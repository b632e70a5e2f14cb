"""chip_smoke.py's phases on the CPU, at the gemma3-1b smoke config.

The device check is steered to the CPU inside each test that needs it
(``chip_smoke.PLATFORM``); unsteered, it must refuse the CPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.SMOKE = True
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    return smoke.serve_phase()


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="not 'tpu'"):
        smoke.require_device()


def test_device_check_steered(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    assert smoke.require_device()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="needs 4 devices"):
        smoke.require_device(4)


def test_replicated_tokens_equal_reference(served):
    res, ref = served
    assert len(res.requests) == 8 and res.matched == 8
    assert res.replicas_identical
    assert res.tokens == ref
    assert all(len(t) == 8 for t in res.tokens)
    # a prompt on each session's first request only
    assert [len(p) for _, p, _ in res.requests] == [16] * 4 + [0] * 4


def test_kernel_digests_equal_numpy(smoke, served):
    got, want, compiled = smoke.kernel_phase(served[0].params)
    assert got == want and len(got) > 1
    assert not compiled      # interpreted on the CPU


def test_script_fails_on_cpu_without_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not 'tpu'" in proc.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


FOUR = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.PLATFORM = "cpu"
    smoke.SMOKE = True
    smoke.main(["--four-chips"])
""")


def test_four_chip_phase_on_virtual_devices(tmp_path):
    script = tmp_path / "four.py"
    script.write_text(FOUR)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, str(script), SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    shards = [l for l in lines if "param_shard_bytes=" in l]
    assert len(shards) == 4
    assert f"cache: {tmp_path / 'cache'}" in lines


def test_compile_cache_placement(monkeypatch):
    import jax
    from repro.launch import compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
