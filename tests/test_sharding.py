"""Distribution correctness: the sharded train step on a small mesh produces
the same numbers as the unsharded one (run in a subprocess so the test
session keeps its single-device view)."""

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
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params
    from repro.models.transformer import lm_loss
    from repro.parallel.sharding import (batch_pspecs, param_pspecs,
                                         shard_ctx_for_mesh)

    out = {}
    for arch in ("qwen3-8b", "qwen3-moe-235b-a22b", "recurrentgemma-2b"):
        cfg = get_smoke_config(arch)
        if cfg.frontend:
            continue
        params = init_params(cfg, jax.random.PRNGKey(0))
        B, S = 4, 16
        inputs = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
        targets = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)

        loss_ref = float(jax.jit(lambda p: lm_loss(cfg, p, inputs, targets))(params))

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = shard_ctx_for_mesh(mesh)
        pspecs = param_pspecs(cfg, params, mesh)
        named = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
        params_sh = jax.tree.map(jax.device_put, params, named)
        loss_sh = float(jax.jit(
            lambda p, i, t: lm_loss(cfg, p, i, t, ctx),
            in_shardings=(named,
                          NamedSharding(mesh, P(("data",))),
                          NamedSharding(mesh, P(("data",)))),
        )(params_sh, inputs, targets))
        out[arch] = (loss_ref, loss_sh)
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_loss_matches_unsharded(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "sharded_check.py"
    script.write_text(SCRIPT)
    proc = subprocess.run(
        [sys.executable, str(script), src],
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert out, "no archs checked"
    for arch, (ref, sh) in out.items():
        assert abs(ref - sh) < 0.05 + 0.02 * abs(ref), (
            f"{arch}: sharded loss {sh} != unsharded {ref}")
