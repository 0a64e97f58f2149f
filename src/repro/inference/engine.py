"""Inference engine: unified training/inference via module reuse (paper §6).

The engine reuses the exact training modules — the KV cache is an
encapsulated component of each token mixer, so the engine only moves opaque
state pytrees. Supports:

  * prefill + single-token decode (``serve_step``),
  * batched generation as ONE device program: a jitted ``lax.scan`` decode
    loop with fused on-device greedy/temperature sampling (PRNG key threaded
    through the carry) — a 256-token generation costs one dispatch + one
    host sync instead of 256,
  * continuous batching: a slot-based scheduler that admits new requests into
    finished slots mid-flight (Orca-style, §6). Admission is a single jitted
    ``admit_fn`` that prefills straight into the batch cache via per-leaf
    ``dynamic_update_slice`` on precomputed batch axes; prompts are padded to
    power-of-two length buckets so the number of compiles is O(log max_len),
    and the ``length`` argument keeps bucket padding out of every mixer's
    cache/recurrent state.

All jitted callables are built once and cached on the engine, so repeated
``generate``/``serve`` calls hit the jit trace cache instead of recompiling.
The decode-step attention kernel is resolved by the kernel registry from
each layer's ``KernelConfig`` (op ``attention.decode``: Pallas flash-decode
where capable, ref otherwise) — a config knob, not a code change (§4.2).

The paged serving subsystem (``repro.serving``: page allocator, chunked
prefill scheduler, streaming gateway) layers on this engine's builders;
models configured with ``kv_cache_layout="paged"`` route ``serve()``
through it automatically.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import REQUIRED, ConfigBase, Required, config_class, visit_config
from repro.core.module import Module, functional, no_context

__all__ = ["InferenceEngine", "Request", "GenerationResult", "sample_tokens",
           "sample_one", "greedy_verify"]

# Smallest admission bucket: prompts pad up to the next power of two >= this.
_MIN_BUCKET = 8


def sample_tokens(logits: jax.Array, key: jax.Array, temperatures: jax.Array,
                  top_ks: jax.Array) -> jax.Array:
    """Per-slot sampling rule of the fused decode step.

    ``logits`` (S, V); ``temperatures`` (S,) with <= 0 meaning exact greedy
    argmax; ``top_ks`` (S,) with <= 0 meaning no top-k filtering. Rows are
    sampled with independent keys split from ``key`` so mixed greedy/sampled
    requests batch into one program.
    """
    S, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.where(top_ks > 0, jnp.minimum(top_ks, V), V)  # (S,)
    sorted_desc = -jnp.sort(-logits, axis=-1)
    thresh = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    filtered = jnp.where(logits >= thresh, logits, -jnp.inf)
    temps = jnp.where(temperatures > 0, temperatures, 1.0)[:, None]
    keys = jax.random.split(key, S)
    sampled = jax.vmap(jax.random.categorical)(keys, filtered / temps)
    return jnp.where(temperatures > 0, sampled.astype(jnp.int32), greedy)


def sample_one(logits: jax.Array, key: jax.Array, temperature: float,
               top_k: int) -> Tuple[int, jax.Array]:
    """Eager single-sequence first-token sampling (prefill/admission path):
    the same rule as :func:`sample_tokens`, returning (token, new_key)."""
    key, sub = jax.random.split(key)
    tok = sample_tokens(logits[None, :], sub,
                        jnp.asarray([temperature], jnp.float32),
                        jnp.asarray([top_k], jnp.int32))
    return int(tok[0]), key


def greedy_verify(logits: jax.Array, draft: jax.Array,
                  n_draft: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Greedy speculative-decoding acceptance rule (device-side).

    ``logits`` (K+1, V) are the model's outputs over the verify window
    ``[t_last, d_1 .. d_K]`` — position i's logits are the model's
    prediction for the token *after* d_i. ``draft`` (K,) holds the
    proposed tokens (entries past ``n_draft`` are ignored). Returns
    ``(tokens, n_accept)``: ``tokens`` (K+1,) is the greedy argmax at
    every position and ``n_accept`` the length of the longest draft
    prefix the model agrees with. Committing ``tokens[:n_accept + 1]``
    — the accepted drafts plus the model's own correction/extension —
    reproduces token-by-token greedy decoding exactly: each accepted
    token is by construction the argmax given all tokens before it.
    """
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = draft.shape[0]
    ok = (g[:k] == draft) & (jnp.arange(k) < n_draft)
    n_accept = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
    return g, n_accept.astype(jnp.int32)


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0  # 0 = no top-k filtering (only applies when sampling)
    arrival_time: float = 0.0


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    tokens: List[int]
    ttft_s: float = 0.0  # time to first token
    tpot_s: float = 0.0  # mean time per output token
    # Deadline-expired: tokens holds whatever was generated before the
    # scheduler cancelled the request (possibly nothing).
    timed_out: bool = False


class InferenceEngine(Module):
    @config_class
    class Config(Module.Config):
        model: Required[ConfigBase] = REQUIRED  # a CausalLM config
        max_len: Required[int] = REQUIRED
        slots: int = 8  # concurrent sequences (continuous batching width)
        eos_token: int = -1  # -1: never stop early
        pad_token: int = 0

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        self._add_child("model", cfg.model)
        self._params = None
        # Jitted callables, built once per engine: repeated generate()/serve()
        # calls reuse the jit trace/compile caches instead of recompiling.
        self._jit_fns: Dict[Any, Callable] = {}

    @no_context
    def uses_paged_cache(self) -> bool:
        """True if any attention layer in the model is configured with the
        paged KV layout (serving then routes through repro.serving)."""
        found = []

        def check(_, c):
            if getattr(c, "kv_cache_layout", None) == "paged":
                found.append(True)

        visit_config(self.config.model, check)
        return bool(found)

    @no_context
    def _check_paged_generate_capacity(self, batch_size: int):
        """generate()/prefill need full-residency identity page tables; a
        pool provisioned below that (the serving configuration) would
        silently drop every KV write. Fail loudly instead."""
        cfg = self.config
        bad = []

        def check(path, c):
            if getattr(c, "kv_cache_layout", None) != "paged" \
                    or c.num_pages is None:
                return  # num_pages=None sizes the pool to full residency
            need = 1 + batch_size * -(-cfg.max_len // c.page_size)
            if c.num_pages < need:
                bad.append(f"{path}: num_pages={c.num_pages} < {need}")

        visit_config(cfg.model, check)
        if bad:
            raise ValueError(
                f"paged KV pool is below full residency for batch "
                f"{batch_size} x max_len {cfg.max_len} — generate() would "
                f"drop KV writes through unmapped page tables. Use the "
                f"serving Scheduler/Gateway (which allocates tables on "
                f"demand) or raise num_pages: {bad[:3]}")

    # ----------------------------------------------------------------- setup

    @no_context
    def load(self, params: Any):
        self._params = params

    @no_context
    def init_cache(self, batch_size: Optional[int] = None):
        cfg = self.config
        B = batch_size or cfg.slots
        cache, _ = functional(self.model, state=self._params,
                              inputs=(B, cfg.max_len), method="init_states")
        return cache

    # ---------------------------------------------------------- pure serving

    @no_context
    def prefill_fn(self) -> Callable:
        """(params, cache, prompt_ids) -> (cache, last_logits)."""
        model = self.model

        def prefill(params, cache, prompt_ids):
            (cache, logits), _ = functional(
                model, state=params,
                inputs={"state": cache, "input_ids": prompt_ids},
                method="prefill")
            return cache, logits[:, -1]

        return prefill

    @no_context
    def serve_step_fn(self) -> Callable:
        """(params, cache, ids_step (B,1)) -> (cache, logits (B,V)).

        ONE new token against a full-length KV cache. The scan decode loop
        and continuous batching both build on it.
        """
        model = self.model

        def serve_step(params, cache, ids_step):
            (cache, logits), _ = functional(
                model, state=params,
                inputs={"state": cache, "ids_step": ids_step},
                method="extend_step")
            return cache, logits[:, -1]

        return serve_step

    def _jit(self, key, builder, **jit_kwargs) -> Callable:
        if key not in self._jit_fns:
            self._jit_fns[key] = jax.jit(builder(), **jit_kwargs)
        return self._jit_fns[key]

    # ------------------------------------------------------------ generation

    @no_context
    def _decode_loop_fn(self, max_new_tokens: int, greedy: bool) -> Callable:
        """(params, cache, logits, key, temperature) -> (cache, tokens (B,N)).

        The whole decode phase as one device program: sample (argmax or
        categorical at ``temperature``) fused with the model's extend_step
        inside a ``lax.scan`` — no per-token host round trip.
        """
        serve_step = self.serve_step_fn()

        def loop(params, cache, logits, key, temperature):
            def sample(logits, key):
                if greedy:
                    return jnp.argmax(logits, axis=-1), key
                key, sub = jax.random.split(key)
                return jax.random.categorical(
                    sub, logits / temperature, axis=-1), key

            def body(carry, _):
                cache, logits, key = carry
                nxt, key = sample(logits, key)
                cache, logits = serve_step(params, cache, nxt[:, None])
                return (cache, logits, key), nxt

            # N-1 scan steps + one final sample: the last sampled token
            # needs no extend_step, so no model forward is wasted on it.
            (cache, logits, key), toks = jax.lax.scan(
                body, (cache, logits, key), None, length=max_new_tokens - 1)
            last, _ = sample(logits, key)
            return cache, jnp.concatenate(
                [jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1)

        return loop

    @no_context
    def generate(self, prompts: np.ndarray, *, max_new_tokens: int = 16,
                 temperature: float = 0.0, seed: int = 0
                 ) -> Tuple[np.ndarray, Dict[str, float]]:
        """Batched generation: one prefill dispatch + one scan-decode
        dispatch. Returns (tokens (B, max_new_tokens), timing metrics)."""
        assert self._params is not None, "call load() first"
        B = prompts.shape[0]
        self._check_paged_generate_capacity(B)
        cache = self.init_cache(B)
        prefill = self._jit("prefill", self.prefill_fn)
        greedy = temperature <= 0
        loop = self._jit(
            ("decode_loop", max_new_tokens, greedy),
            lambda: self._decode_loop_fn(max_new_tokens, greedy),
            donate_argnums=(1,))

        t0 = time.perf_counter()
        cache, logits = prefill(self._params, cache, jnp.asarray(prompts))
        logits.block_until_ready()
        ttft = time.perf_counter() - t0

        key = jax.random.PRNGKey(seed)
        temp = jnp.asarray(temperature if not greedy else 1.0, jnp.float32)
        t1 = time.perf_counter()
        cache, tokens = loop(self._params, cache, logits, key, temp)
        tokens.block_until_ready()
        dt = time.perf_counter() - t1
        tpot = dt / max_new_tokens
        return np.asarray(tokens), {
            "ttft_s": ttft, "tpot_s": tpot,
            "throughput_tok_s": B * max_new_tokens / max(dt, 1e-9)}

    # ---------------------------------------------------- continuous batching

    @no_context
    def batch_axes(self):
        """Per-leaf batch-axis map: the axis where init_cache shapes at two
        different batch sizes differ (-1 = no batch axis / shared leaf, e.g.
        a paged KV pool of fixed ``num_pages``). Caches are opaque pytrees;
        this is the only structural fact admission splicing needs.

        Detection compares B=1 against B=max(slots, 2): comparing 1 vs 1
        (a single-slot engine) would see identical shapes everywhere and
        silently mark every leaf shared — dropping the admission splice.
        """
        cfg = self.config
        model = self.model

        def shapes(B):
            f = lambda: functional(model, state=self._params,  # noqa: E731
                                   inputs=(B, cfg.max_len), method="init_states")[0]
            return jax.eval_shape(f)

        s1, sN = shapes(1), shapes(max(cfg.slots, 2))

        def axis(a, b):
            for i, (x, y) in enumerate(zip(a.shape, b.shape)):
                if x != y:
                    return i
            return -1  # no batch axis (shared leaf)

        return jax.tree.map(axis, s1, sN)

    def _bucket_len(self, n: int) -> int:
        """Power-of-two admission buckets: prompts of any length compile
        O(log n) prefill shapes. Buckets may exceed max_len — the ring cache
        keeps the last T valid tokens (and recurrent mixers consume the full
        prompt), same as batched generation with an over-long prompt."""
        b = _MIN_BUCKET
        while b < n:
            b *= 2
        return b

    @no_context
    def _admit_fn(self) -> Callable:
        """(params, batch_cache, padded_prompt (1,L), prompt_len, slot)
        -> (batch_cache, last_logits (V,)).

        One jitted program per bucket L: prefills a fresh single-slot cache
        (bucket padding excluded via ``length``) and splices every leaf into
        the batch cache with ``dynamic_update_slice`` on its batch axis.
        ``prompt_len`` and ``slot`` are traced scalars — admitting into a
        different slot or with a different true length never recompiles.
        """
        cfg = self.config
        model = self.model
        axes = self.batch_axes()

        def admit(params, batch_cache, padded_prompt, prompt_len, slot):
            c1, _ = functional(model, state=params,
                               inputs=(1, cfg.max_len), method="init_states")
            (c1, logits), _ = functional(
                model, state=params,
                inputs={"state": c1, "input_ids": padded_prompt,
                        "length": prompt_len},
                method="prefill")
            last = jax.lax.dynamic_index_in_dim(
                logits, prompt_len - 1, axis=1, keepdims=False)  # (1, V)

            def splice(bc, c, ax):
                if ax < 0:
                    return bc
                return jax.lax.dynamic_update_slice_in_dim(
                    bc, c.astype(bc.dtype), slot, axis=ax)

            new_cache = jax.tree.map(splice, batch_cache, c1, axes)
            return new_cache, last[0]

        return admit

    @no_context
    def _serve_decode_fn(self, sampling: bool = False) -> Callable:
        """Fused decode step for continuous batching.

        ``sampling=False``: (params, cache, ids_step (S,1)) ->
        (cache, next_tokens (S,)) — greedy argmax fused into the step so the
        host transfers S ints instead of the full (S, V) logits.

        ``sampling=True``: (params, cache, ids_step, key, temperatures (S,),
        top_ks (S,), active (S,) bool) -> (cache, next_tokens, new_key) —
        per-slot temperature/top-k sampling fused on device
        (:func:`sample_tokens`); rows with temperature <= 0 stay exact
        greedy. Inactive slots keep their pre-step state: every per-slot
        cache leaf is selected back to its old value, so a slot that is
        empty or mid-chunked-prefill is not advanced by the pad token fed
        in its row. (Shared page-pool leaves pass through: an inactive
        slot's write lands in its own pages at the position its next real
        chunk overwrites before attending — or is dropped outright if that
        page is unmapped — so pools self-heal.)
        """
        serve_step = self.serve_step_fn()

        if not sampling:
            def decode(params, cache, ids_step):
                cache, logits = serve_step(params, cache, ids_step)
                return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

            return decode

        axes = self.batch_axes()

        def decode_sampling(params, cache, ids_step, key, temperatures,
                            top_ks, active):
            new_cache, logits = serve_step(params, cache, ids_step)

            def sel(new, old, ax):
                if ax < 0:
                    return new
                shape = [1] * new.ndim
                shape[ax] = active.shape[0]
                return jnp.where(active.reshape(shape), new, old)

            new_cache = jax.tree.map(sel, new_cache, cache, axes)
            key, sub = jax.random.split(key)
            toks = sample_tokens(logits, sub, temperatures, top_ks)
            return new_cache, toks, key

        return decode_sampling

    @no_context
    def serve(self, requests: List[Request], *, seed: int = 0
              ) -> List[GenerationResult]:
        """Slot-based continuous batching.

        All slots decode together each step; finished slots are refilled from
        the queue via the jitted bucketed ``admit_fn`` (no recompiles once
        the touched buckets are warm). Per-slot cache positions
        ("pos"/"index") make mid-flight admission exact. Model code is
        untouched — the cache is an opaque pytree (paper §6).

        Per-request ``temperature``/``top_k`` are honored slot-wise inside
        the fused decode step (:func:`sample_tokens`): requests with
        temperature 0 decode exact greedy while sampled requests share the
        same batch. Models with ``kv_cache_layout="paged"`` delegate to the
        iteration-level :class:`repro.serving.Scheduler` (chunked prefill +
        page allocation — the dense slot path here would drop pool writes).
        """
        assert self._params is not None
        if self.uses_paged_cache():
            from repro.serving.scheduler import Scheduler, ServeRequest

            sched = Scheduler(self, seed=seed)
            return sched.run([
                ServeRequest(request_id=r.request_id, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens,
                             temperature=r.temperature, top_k=r.top_k,
                             arrival_time=r.arrival_time)
                for r in requests])
        cfg = self.config
        S = cfg.slots
        # Stable FCFS: ties on arrival_time (the common case for batch
        # submission) keep request order instead of Python-sort whims.
        queue = sorted(requests, key=lambda r: (r.arrival_time, r.request_id))
        results: Dict[int, GenerationResult] = {}
        key = jax.random.PRNGKey(seed)

        admit_fn = self._jit("admit", self._admit_fn, donate_argnums=(1,))
        decode = self._jit("serve_decode_sampling",
                           lambda: self._serve_decode_fn(sampling=True),
                           donate_argnums=(1,))
        params = self._params

        batch_cache = self.init_cache(S)
        slot_req: List[Optional[Request]] = [None] * S
        slot_tokens: List[List[int]] = [[] for _ in range(S)]
        slot_t0: List[float] = [0.0] * S

        def admit(slot: int, req: Request):
            nonlocal batch_cache, key
            n = len(req.prompt)
            L = self._bucket_len(n)
            padded = np.full((1, L), cfg.pad_token, np.int32)
            padded[0, :n] = req.prompt
            t0 = time.perf_counter()
            batch_cache, logits = admit_fn(
                params, batch_cache, jnp.asarray(padded),
                jnp.asarray(n, jnp.int32), jnp.asarray(slot, jnp.int32))
            tok0, key = sample_one(logits, key, req.temperature, req.top_k)
            ttft = time.perf_counter() - t0
            results[req.request_id] = GenerationResult(req.request_id, [],
                                                       ttft_s=ttft)
            if tok0 == cfg.eos_token or req.max_new_tokens <= 1:
                # Done at the first token: don't occupy a decode slot. The
                # prefill was the whole per-token cost, so tpot = ttft
                # rather than a missing 0.0.
                results[req.request_id].tokens = [tok0]
                results[req.request_id].tpot_s = ttft
                return
            slot_req[slot] = req
            slot_tokens[slot] = [tok0]
            slot_t0[slot] = time.perf_counter()

        while queue or any(r is not None for r in slot_req):
            # Admit into free slots (an admission that finishes at its
            # first token leaves the slot free for the next request).
            for s in range(S):
                while slot_req[s] is None and queue:
                    admit(s, queue.pop(0))
            active = [s for s in range(S) if slot_req[s] is not None]
            if not active:
                break
            last = np.asarray(
                [[slot_tokens[s][-1] if slot_req[s] is not None else cfg.pad_token]
                 for s in range(S)], np.int32)
            temps = np.asarray(
                [slot_req[s].temperature if slot_req[s] is not None else 0.0
                 for s in range(S)], np.float32)
            topks = np.asarray(
                [slot_req[s].top_k if slot_req[s] is not None else 0
                 for s in range(S)], np.int32)
            occupied = np.asarray([slot_req[s] is not None for s in range(S)])
            batch_cache, nxt_dev, key = decode(
                params, batch_cache, jnp.asarray(last), key,
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(occupied))
            nxt = np.asarray(nxt_dev)
            for s in active:
                req = slot_req[s]
                slot_tokens[s].append(int(nxt[s]))
                done = (len(slot_tokens[s]) >= req.max_new_tokens or
                        int(nxt[s]) == cfg.eos_token)
                if done:
                    res = results[req.request_id]
                    res.tokens = slot_tokens[s][:req.max_new_tokens]
                    dt = time.perf_counter() - slot_t0[s]
                    res.tpot_s = dt / max(len(res.tokens) - 1, 1)
                    slot_req[s] = None
        return [results[r.request_id] for r in requests]
