"""End-to-end behaviour tests for the paper's system."""

import os
import subprocess
import sys


def test_quickstart_example_runs():
    # the child stays on the CPU: it never loads the TPU library, whose
    # lock a compile test of this suite may hold; and the suite writes
    # no persistent compile cache
    out = subprocess.run(
        [sys.executable, "examples/quickstart.py"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu",
             "JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "phase-1" in out.stdout
    assert "covers truth: True" in out.stdout


def test_data_pipeline_determinism():
    from repro.configs import get_config
    from repro.data.synthetic import make_pipeline
    import numpy as np
    cfg = get_config("llama3.2-3b", smoke=True)
    p1 = make_pipeline(cfg, 64, 4, seed=7)
    p2 = make_pipeline(cfg, 64, 4, seed=7)
    b1, b2 = p1.batch(12), p2.batch(12)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = p1.batch(13)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_mini_training_descends_and_resumes(tmp_path):
    """Loss descends; a killed-and-restarted run continues bit-exact data."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.data.synthetic import make_pipeline
    from repro.models.registry import init_params, loss_fn
    from repro.optim import AdamW, apply_updates
    from repro.runtime.checkpoint import restore_checkpoint, save_checkpoint

    cfg = get_config("llama3.2-3b", smoke=True)
    pipe = make_pipeline(cfg, 64, 4)
    opt = AdamW(lr=5e-3)
    lfn = loss_fn(cfg)

    @jax.jit
    def step_fn(p, s, batch):
        loss, g = jax.value_and_grad(lfn)(p, batch)
        u, s = opt.update(g, s, p)
        return apply_updates(p, u), s, loss

    params = init_params(cfg, jax.random.PRNGKey(0))
    state = opt.init(params)
    losses = []
    for step in range(8):
        params, state, loss = step_fn(params, state, pipe.batch(step))
        losses.append(float(loss))
        if step == 4:
            save_checkpoint(tmp_path, step, (params, state),
                            extra={"step": step})
    assert losses[-1] < losses[0]

    # restart from step 5 and verify identical continuation
    (p2, s2), extra = restore_checkpoint(tmp_path, (params, state))
    start = extra["step"] + 1
    for step in range(start, 8):
        p2, s2, loss2 = step_fn(p2, s2, pipe.batch(step))
    np.testing.assert_allclose(float(loss2), losses[-1], rtol=1e-4)
