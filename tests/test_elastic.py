"""Elastic scaling: a checkpoint saved on one layout resumes on a different
mesh (re-sharded) with identical loss — the re-mesh event a 1000-node job
hits when its pod allocation changes."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, sys.argv[1])
    ckpt_dir = sys.argv[2]
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params
    from repro.models.transformer import lm_loss
    from repro.checkpoint import save_checkpoint, load_checkpoint, reshard
    from repro.parallel.sharding import param_pspecs, shard_ctx_for_mesh

    cfg = get_smoke_config("qwen3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 16
    inputs = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    targets = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    loss0 = float(jax.jit(lambda p: lm_loss(cfg, p, inputs, targets))(params))
    fp = save_checkpoint(ckpt_dir, 3, params)

    # "restart" on a different mesh: 2x4 instead of single-device
    step, p2, _ = load_checkpoint(ckpt_dir, expect_fp=fp)
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = shard_ctx_for_mesh(mesh)
    pspecs = param_pspecs(cfg, p2, mesh)
    p_sharded = reshard(p2, mesh, pspecs)
    loss1 = float(jax.jit(lambda p, i, t: lm_loss(cfg, p, i, t, ctx))(
        p_sharded, inputs, targets))
    print("RESULT:" + json.dumps([loss0, loss1]))
""")


@pytest.mark.slow
def test_checkpoint_reshards_onto_new_mesh(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "elastic_check.py"
    script.write_text(SCRIPT)
    proc = subprocess.run(
        [sys.executable, str(script), src, str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    loss0, loss1 = json.loads(line[0][len("RESULT:"):])
    assert abs(loss0 - loss1) < 0.05 + 0.02 * abs(loss0)
