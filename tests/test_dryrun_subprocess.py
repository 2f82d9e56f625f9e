"""Real multi-device GSPMD execution + dry-run lowering, in a subprocess
(XLA device count is locked at first init, so the 8-device test must not
share the main pytest process)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.jobs import LoRAJobSpec
    from repro.core.ssm import SharedSuperModel
    from repro.data.pipeline import FusedBatcher
    from repro.optim import adamw
    from repro.launch.mesh import make_local_mesh
    from repro.optim.schedule import constant
    from repro.sharding import rules, use_mesh

    mesh = make_local_mesh(model=2)          # (4, 2), Auto axes for GSPMD
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    cfg = get_config("tinyllama-1.1b").reduced()
    jobs = [LoRAJobSpec("a", rank=4, batch_size=2, seq_len=32),
            LoRAJobSpec("b", rank=8, batch_size=2, seq_len=32)]
    ssm = SharedSuperModel(cfg, jobs, impl="xla", block_t=8)
    params, adapters = ssm.init(jax.random.PRNGKey(0))
    opt = adamw.init(adapters)
    fb = FusedBatcher(jobs, cfg.vocab_size, block_t=8)
    batch = {k: jnp.asarray(v) for k, v in fb.next_batch().items()}

    p_sh = rules.param_shardings(mesh, params)
    a_sh = rules.replicated(mesh, adapters)
    o_sh = rules.replicated(mesh, opt)
    b_sh = rules.batch_shardings(mesh, batch)

    step = ssm.make_train_step(lr_fn=constant(1e-3))
    with mesh, use_mesh(mesh):
        jitted = jax.jit(step, in_shardings=(p_sh, a_sh, o_sh, b_sh))
        # REAL sharded execution on 8 host devices
        params_s = jax.device_put(params, p_sh)
        batch_s = jax.device_put(batch, b_sh)
        ad2, opt2, m = jitted(params_s, adapters, opt, batch_s)
        loss = float(m["loss"])
        assert np.isfinite(loss), loss

        # same step UNSHARDED single-device for numerical comparison
        step1 = jax.jit(ssm.make_train_step(lr_fn=constant(1e-3)))
        _, _, m1 = step1(params, adapters, opt, batch)
        np.testing.assert_allclose(loss, float(m1["loss"]), rtol=2e-2)

        # decode path lowers + runs sharded
        shape = InputShape("d", 64, 4, "decode")
        caches = ssm.init_decode_caches(shape, batch=4)
        serve = jax.jit(ssm.make_serve_step())
        logits, _ = serve(params_s, adapters, caches,
                          {"tokens": jnp.ones((4, 1), jnp.int32),
                           "adapter_ids": jnp.asarray([0, 0, 1, 1],
                                                      jnp.int32)}, 5)
        assert np.isfinite(np.asarray(logits)).all()
    print("SUBPROCESS_OK", loss)
""")


def test_sharded_train_step_8dev():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SUBPROCESS_OK" in r.stdout


def test_production_dryrun_one_pair():
    """One real (arch x shape) pair through the production 512-device
    dry-run path — proves deliverable (e) machinery end to end."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-360m", "--shape", "decode_32k"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert "OK" in r.stdout
