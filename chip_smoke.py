#!/usr/bin/env python3
"""Smoke test of the system on a TPU: the quickest proof that it still
starts on the chip and gives the right answers there.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded-loss phase only

One chip runs two phases:

* serve — ``repro.launch.serve`` at the full width of gemma3-1b (random
  weights from ``PRNGKey(0)``): 4 sessions, 8 requests, each ordered by uBFT
  and decoded by all 2f+1 = 3 replicas.  Every reply must be the
  f+1-matched one, the replicas' snapshots identical, and each request's
  tokens equal to the unreplicated reference (the same compiled
  prefill/decode called directly on the same history, outside consensus).
* kernel — the served parameters fingerprinted by
  ``attest_batch(backend="pallas")``, which must run the compiled kernel
  (``tpu_custom_call`` in the program) and agree with ``attest_words_np``.

``--four-chips`` shards full-width gemma3-1b over a (2, 2) data × model
mesh and checks its loss against the unsharded loss on chip 0.

Everything runs in this one process.  The last line of standard output is
``{"ok": true, "device": {...}}``; any failed check, or a first device that
is not a TPU, exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit(f"chip_smoke: no src/repro beside {__file__}; run it from a "
             "checkout of the repo")
sys.path.insert(0, SRC)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.crypto import attest_batch, attest_words_np  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: the platform the device check demands (tests steer it to "cpu")
PLATFORM = "tpu"
ARCH = "gemma3-1b"
#: run ARCH's CPU-sized smoke config instead of its full width (tests only)
SMOKE = False


def check(cond: bool, what: str) -> None:
    """A failed check exits non-zero with the message on stderr."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_device(n: int = 1):
    devs = jax.devices()
    check(devs[0].platform == PLATFORM,
          f"first device is {devs[0].platform!r}, not {PLATFORM!r}")
    check(len(devs) >= n, f"needs {n} devices, found {len(devs)}")
    return devs


def _mem(dev, key: str) -> str:
    stats = dev.memory_stats() or {}
    return str(stats[key]) if key in stats else "not reported"


def reference_tokens(decode, requests):
    """Unreplicated reference: the same requests, in the same order, fed
    to the decode function directly on each session's history."""
    hist: dict = {}
    out = []
    for sid, prompt, n in requests:
        h = hist.setdefault(sid, [])
        h.extend(prompt)
        toks = decode(sid, list(h), n)
        h.extend(toks)
        out.append(toks)
    return out


def serve_phase():
    """The replicated token server, checked; returns (result, reference)."""
    args = serve.parse_args(
        ["--arch", ARCH, "--batch", "4", "--requests", "8",
         "--prompt-len", "16", "--gen", "8"] + (["--smoke"] if SMOKE else []))
    res = serve.run(args)
    ref = reference_tokens(res.decode, res.requests)
    dev = jax.devices()[0]
    pbytes = sum(x.nbytes for x in jax.tree.leaves(res.params))
    print(f"serve: arch={res.cfg.name} device_kind={dev.device_kind} "
          f"param_bytes={pbytes} "
          f"peak_bytes_in_use={_mem(dev, 'peak_bytes_in_use')} "
          f"compile_s={res.compile_s} wall_s={res.wall_s}")
    for r, ((sid, _, _), lat) in enumerate(zip(res.requests,
                                               res.smr_latency_us)):
        print(f"serve: req={r} session={sid} smr_latency_us={lat} "
              "(simulated)")
    check(res.matched == len(res.requests) and None not in res.tokens,
          f"{res.matched}/{len(res.requests)} replies f+1-matched")
    check(res.replicas_identical, "replica snapshots differ")
    check(res.tokens == ref,
          f"replicated tokens {res.tokens} != reference {ref}")
    return res, ref


def kernel_phase(params):
    """Fingerprint every parameter with the Pallas kernel and with the
    numpy reference.  Returns (kernel digests, reference digests, whether
    the kernel was compiled rather than interpreted)."""
    words = jax.jit(ops.to_words)
    got, want = [], []
    for leaf in jax.tree.leaves(params):
        w = words(leaf)
        got.append(attest_batch([w], backend="pallas")[0])
        host = np.asarray(leaf)
        want.append(attest_words_np(
            host.view(np.uint16 if host.itemsize == 2 else np.uint32)))
    compiled = "tpu_custom_call" in ops.fingerprint.lower(w).as_text()
    n_words = sum(x.size for x in jax.tree.leaves(params))
    print(f"kernel: leaves={len(got)} words={n_words} compiled={compiled} "
          f"peak_bytes_in_use={_mem(jax.devices()[0], 'peak_bytes_in_use')}")
    check(got == want, "Pallas digests differ from attest_words_np")
    return got, want, compiled


def four_chip_phase(devices):
    """Loss of the sharded model on a (2, 2) mesh vs the unsharded loss on
    the first device.  Returns (unsharded, sharded)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params
    from repro.models.transformer import lm_loss
    from repro.parallel.sharding import named, param_pspecs, shard_ctx_for_mesh

    cfg = get_smoke_config(ARCH) if SMOKE else get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 64
    inputs = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    targets = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    t = time.perf_counter()
    loss_ref = float(jax.jit(
        lambda p, i, tg: lm_loss(cfg, p, i, tg))(params, inputs, targets))
    ref_s = time.perf_counter() - t

    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    ctx = shard_ctx_for_mesh(mesh)
    shardings = named(mesh, param_pspecs(cfg, params, mesh))
    params_sh = jax.device_put(params, shardings)
    del params
    rows = NamedSharding(mesh, P("data"))
    t = time.perf_counter()
    loss_sh = float(jax.jit(
        lambda p, i, tg: lm_loss(cfg, p, i, tg, ctx),
        in_shardings=(shardings, rows, rows))(params_sh, inputs, targets))
    sh_s = time.perf_counter() - t

    shard_bytes = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params_sh):
        for s in leaf.addressable_shards:
            shard_bytes[s.device] += s.data.nbytes
    print(f"four-chips: arch={cfg.name} mesh=(data=2, model=2) "
          f"loss_unsharded={loss_ref} loss_sharded={loss_sh} "
          f"first_call_s unsharded={ref_s} sharded={sh_s}")
    for d, nb in shard_bytes.items():
        print(f"four-chips: device={d.id} param_shard_bytes={nb} "
              f"bytes_in_use={_mem(d, 'bytes_in_use')}")
    check(abs(loss_ref - loss_sh) < 0.05 + 0.02 * abs(loss_ref),
          f"sharded loss {loss_sh} != unsharded {loss_ref}")
    total = sum(x.nbytes for x in jax.tree.leaves(params_sh))
    check(max(shard_bytes.values()) < total,
          "parameters are not spread over the mesh")
    return loss_ref, loss_sh


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-unsharded loss phase on "
                         "a (2, 2) mesh of four chips")
    args = ap.parse_args(argv)
    devices = require_device(4 if args.four_chips else 1)
    print(f"cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chip_phase(devices)
    else:
        res, _ = serve_phase()
        _, _, compiled = kernel_phase(res.params)
        check(compiled, "fingerprint kernel ran interpreted, not compiled")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
