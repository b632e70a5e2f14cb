"""Pipeline parallelism: GPipe schedule over a stage axis == sequential."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import pipeline_apply

    S, n_micro, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, d, d)) / d ** 0.5
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    mesh = make_mesh((4,), ("stage",))
    out = pipeline_apply(stage_fn, ws, x, mesh)

    ref = x
    for i in range(S):
        ref = jnp.tanh(ref @ ws[i])
    err = float(jnp.max(jnp.abs(out - ref)))
    print("RESULT:" + str(err))
""")


@pytest.mark.slow
def test_pipeline_matches_sequential(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "pipe_check.py"
    script.write_text(SCRIPT)
    proc = subprocess.run([sys.executable, str(script), src],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    assert float(line[0][len("RESULT:"):]) < 1e-5
