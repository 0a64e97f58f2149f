"""The model work a serving window did, from the program's spans and the
token timestamps: prefill chunks and decode tokens with their positions."""

from typing import Iterator, Tuple


def chunks(ctx) -> Iterator[Tuple[int, int]]:
    """(offset, length) of every prefill chunk that ended in the window,
    from the scheduler's ``prefill_chunk`` spans (offsets accumulate per
    request in the order the chunks ran)."""
    rec = ctx["records"]
    lo, hi = rec["t_open"] * 1e6, rec["t_close"] * 1e6
    done = {}
    for ev in rec["spans"]:
        if ev.get("name") != "prefill_chunk":
            continue
        rid, c = ev["args"]["request_id"], int(ev["args"]["chunk"])
        off = done.get(rid, 0)
        done[rid] = off + c
        if lo <= ev["ts"] + ev["dur"] < hi:
            yield off, c


def decode_positions(ctx) -> Iterator[int]:
    """Position of the token each decode step fed, for every output token
    after the first emitted in the window (the first comes from the last
    prefill chunk)."""
    rec = ctx["records"]
    lo, hi = rec["t_open"], rec["t_close"]
    for r in rec["all_requests"]:
        n = len(r["prompt"])
        for i, t in enumerate(r["times"]):
            if i and lo <= t < hi:
                yield n + i - 1


def have_spans(ctx) -> bool:
    return any(ev.get("name") == "prefill_chunk"
               for ev in ctx["records"]["spans"])
