"""The control, the plain reference computed with float8 matrix products
(the precision below the configurations' bfloat16), comes out not
correct against the limits; at small widths on the CPU."""

import numpy as np

from chipbench import harness, program, tiny


def test_training_control_fails_a_limit():
    import jax

    ctx = tiny.context(tiny.TRAIN)
    ctx["devices"] = jax.devices()[:1]
    drv = harness.load_module(f"{harness.HERE}/drivers/train.py")
    ref = program.reference(ctx["config"])
    want = drv.reference_readings(ctx, ref)
    ctrl = drv.gaps(drv.reference_readings(ctx, ref, mm="fp8"), want)
    same = drv.gaps(drv.reference_readings(ctx, ref), want)
    assert all(v == 0 for v in same.values())
    assert any(v > ctx["limits"][k] for k, v in ctrl.items()), ctrl


def test_serving_control_fails_the_limit():
    import jax

    ctx = tiny.context(tiny.SERVE)
    drv = harness.load_module(f"{harness.HERE}/drivers/serve.py")
    ref = program.reference(ctx["config"])
    weights = ref.make_weights(ctx["config"], ctx["seed"], jax.numpy.bfloat16)
    rng = np.random.default_rng(0)
    recs = [{"prompt": rng.integers(1, 512, size=n, dtype=np.int32),
             "tokens": list(rng.integers(1, 512, size=48))}
            for n in (20, 100, 150)]
    out = drv.control_readings(ctx, ref, weights, recs)
    limit = ctx["limits"]["served_logit_gap"]
    assert out["control_fp8"]["served_logit_gap"] > limit, out
    assert out["fault_altered_token"]["served_logit_gap"] > limit, out
