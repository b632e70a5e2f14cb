"""Batched event core + vectorized crypto: batch ≡ scalar, bit-for-bit.

Every batch API added for the engine-floor work must be *observationally
identical* to the scalar loop it replaced — same bytes, same verdicts,
same ``(time, seq)`` execution order.  These tests pin that contract:

* ``fingerprint_batch`` / ``checksum_batch`` / ``sign_batch`` /
  ``verify_batch`` ≡ their scalar forms across shapes, lengths, empty
  and singleton batches, on every backend;
* the lane-wise numpy SHA-256 matches hashlib across message-schedule
  block boundaries (the padding edge cases live at 55/56/63/64/119/120);
* the Pallas attestation kernel matches the numpy Weyl reference;
* ``registers._unpack_batch`` ≡ ``_unpack`` including corrupt blobs;
* ``Simulator.push_run`` / ``NetworkModel.send_fanout`` preserve the
  exact event order and jitter stream of n individual sends;
* ``Cluster.stats()["engine"]`` proves the batched paths run hot.

Hypothesis deepens the sweep when installed; the explicit cases below
cover the boundaries regardless.
"""

import struct

import numpy as np
import pytest

from repro.core import crypto
from repro.core.registers import _pack, _unpack, _unpack_batch
from repro.sim.events import Process, Simulator
from repro.sim.net import NetParams, NetworkModel

# -- edge-length corpus: SHA-256 pads to 64 B blocks with 9 B overhead, so
# the interesting lengths straddle 55/56 (1 vs 2 blocks) and 119/120.
EDGE_LENGTHS = [0, 1, 3, 31, 32, 54, 55, 56, 57, 63, 64, 65,
                118, 119, 120, 121, 127, 128, 129, 200, 1000]


def _corpus():
    rng = np.random.default_rng(42)
    return [bytes(rng.integers(0, 256, size=n, dtype=np.uint8).tobytes())
            for n in EDGE_LENGTHS]


# ---------------------------------------------------------------- digests
@pytest.mark.parametrize("backend", ["hashlib", "numpy", None])
def test_fingerprint_batch_equals_scalar(backend):
    datas = _corpus()
    want = [crypto.fingerprint(d) for d in datas]
    assert crypto.fingerprint_batch(datas, backend=backend) == want


@pytest.mark.parametrize("backend", ["hashlib", "numpy"])
def test_fingerprint_batch_empty_and_singleton(backend):
    assert crypto.fingerprint_batch([], backend=backend) == []
    one = [b"solo"]
    assert crypto.fingerprint_batch(one, backend=backend) == \
        [crypto.fingerprint(b"solo")]


def test_numpy_sha256_across_block_counts():
    # mixed batch: 1-block, 2-block, 3-block and 17-block lanes together —
    # the short-lane freeze (np.where) must not corrupt longer lanes
    datas = [b"a" * 10, b"b" * 100, b"c" * 170, b"d" * 1050]
    import hashlib
    assert crypto._sha256_batch_np(datas) == \
        [hashlib.sha256(d).digest() for d in datas]


def test_fingerprint_batch_cached_writes_back_and_hits():
    objs = [("win", i, b"x" * i) for i in range(6)]
    fresh = crypto.fingerprint_batch_cached(objs)
    assert fresh == [crypto.fingerprint_cached(o) for o in objs]
    before = crypto.digest_stats()["batch_fingerprint_hits"]
    again = crypto.fingerprint_batch_cached(objs)
    assert again == fresh
    assert crypto.digest_stats()["batch_fingerprint_hits"] >= before + len(objs)


def test_checksum_batch_equals_scalar():
    datas = _corpus()
    assert crypto.checksum_batch(datas) == [crypto.checksum(d) for d in datas]
    assert crypto.checksum_bytes_batch(datas) == \
        [crypto.checksum_bytes(d) for d in datas]
    assert crypto.checksum_batch([]) == []
    assert crypto.checksum_bytes_batch([]) == []


def test_wire_size_and_encode_batch_equal_scalar():
    objs = [(), (1,), ("REQ", b"x" * 9, 3.5), ((1, 2), (b"n", -7)), b"raw"]
    assert crypto.wire_size_batch(objs) == \
        [crypto.wire_size_cached(o) for o in objs]
    assert crypto.encode_batch_cached(objs) == \
        [crypto.encode_cached(o) for o in objs]


# ------------------------------------------------------------------- MACs
def test_sign_and_verify_batch_equal_scalar():
    reg = crypto.KeyRegistry()
    s1 = reg.keygen("p1")
    s2 = reg.keygen("p2")
    payloads = [("certify", v, v * 7, b"fp" * 8) for v in range(5)]
    sigs = s1.sign_batch(payloads)
    assert sigs == [s1.sign(p) for p in payloads]

    items = [("p1", p, sig) for p, sig in zip(payloads, sigs)]
    # forgery: p2's MAC over the same payload must not verify as p1's
    items.append(("p1", payloads[0], s2.sign(payloads[0])))
    # tamper: valid MAC, different payload
    items.append(("p1", ("certify", 99, 0, b"zz"), sigs[0]))
    got = reg.verify_batch(items)
    assert got == [reg.verify(pid, p, sig) for pid, p, sig in items]
    assert got == [True] * 5 + [False, False]
    assert reg.verify_batch([]) == []


# ------------------------------------------------- attestation (Pallas)
def test_attest_batch_numpy_reference():
    arrays = [np.arange(n, dtype=np.uint32) for n in (0, 1, 7, 4096, 5000)]
    got = crypto.attest_batch(arrays, backend="numpy")
    for a, g in zip(arrays, got):
        # independent scalar reference of the Weyl mix
        acc = 0
        for w in a.tolist():
            acc = (acc + (((w * crypto.MIX32) & 0xFFFFFFFF) ^ (w >> 16))) \
                & 0xFFFFFFFF
        assert g == acc


#: words one grid step of the fingerprint kernel reads
#: (``BLOCK_ROWS * LANES`` of repro.kernels.fingerprint; kept literal so
#: collecting this file does not import JAX)
_FP_BLOCK = 2048 * 128


@pytest.mark.slow
@pytest.mark.parametrize("sizes", [
    (0,),                                   # empty array
    (1, 5, 127, 1000),                      # under one (8, 128) tile
    (128, 4096, 8192),                      # whole rows, under one block
    (129, 4097, 10_000),                    # not a multiple of 128
    (_FP_BLOCK, 2 * _FP_BLOCK + 77),        # one block; ragged last block
], ids=["empty", "under_tile", "whole_rows", "ragged_rows", "multi_block"])
def test_attest_batch_pallas_parity(sizes):
    pytest.importorskip("jax")
    rng = np.random.default_rng(7)
    arrays = [rng.integers(0, 2**32, size=n, dtype=np.uint32) for n in sizes]
    assert crypto.attest_batch(arrays, backend="pallas") == \
        crypto.attest_batch(arrays, backend="numpy")


# --------------------------------------------------- register validation
def test_unpack_batch_equals_scalar():
    good = [_pack(ts, bytes([ts]) * ts) for ts in range(5)]
    corrupt = good[2][:8] + b"\x00" + good[2][9:]       # checksum mismatch
    short = good[1][:10]                                 # below BLOB_HEADER
    truncated = good[3][:-1]                             # ln > len(value)
    blobs = good + [corrupt, short, truncated, None, b""]
    assert _unpack_batch(blobs) == [_unpack(b) for b in blobs]
    assert _unpack_batch([]) == []


# ------------------------------------------------------- event-core order
def test_push_run_preserves_time_seq_order():
    sim = Simulator(seed=0)
    order = []
    sim.at(1.0, lambda: order.append("before"))
    # a same-timestamp scalar event pushed BEFORE the run must sort first,
    # one pushed AFTER must sort after the whole run
    sim.at(2.0, lambda: order.append("a"))
    sim.push_run(2.0, [lambda: order.append("r1"),
                       lambda: order.append("r2"),
                       lambda: order.append("r3")])
    sim.at(2.0, lambda: order.append("z"))
    sim.run()
    assert order == ["before", "a", "r1", "r2", "r3", "z"]
    # each run member counts as one event, like n individual pushes
    assert sim.events_processed == 6


def test_push_run_respects_until_and_pred():
    sim = Simulator(seed=0)
    order = []
    sim.push_run(5.0, [lambda i=i: order.append(i) for i in range(3)])
    sim.run(until=4.0)
    assert order == [] and sim.now == 4.0
    hit = sim.run_until(lambda: len(order) >= 3, timeout=100.0)
    assert hit and order == [0, 1, 2]


class _Sink(Process):
    def __init__(self, sim, pid, log):
        super().__init__(sim, pid)
        self.log = log

    def on_message(self, src, msg):
        self.log.append((self.pid, src, msg, self.sim.now))


def _fanout_rig(sigma):
    sim = Simulator(seed=123)
    net = NetworkModel(sim, NetParams(jitter_sigma=sigma))
    log = []
    for i in range(4):
        _Sink(sim, f"p{i}", log)
    return sim, net, log


@pytest.mark.parametrize("sigma", [0.0, 0.08])
def test_send_fanout_bit_identical_to_scalar_sends(sigma):
    dsts = ["p0", "p1", "p2", "p3"]
    runs = []
    for use_fanout in (False, True):
        sim, net, log = _fanout_rig(sigma)
        sim.processes["p2"].crash()       # crashed dst: jitter still drawn
        if use_fanout:
            net.send_fanout("p0", dsts, ("M", 1), 64)
        else:
            for d in dsts:
                net.send("p0", d, ("M", 1), 64)
        net.send("p0", "p1", ("TAIL", 2), 32)  # stream must stay aligned
        sim.run()
        runs.append((log, net.msgs_sent, net.bytes_sent, sim.events_processed))
    assert runs[0] == runs[1]


def test_send_fanout_coalesces_at_zero_jitter():
    sim, net, log = _fanout_rig(0.0)
    net.send_fanout("p0", ["p1", "p2", "p3"], "hi", 10)
    assert net.coalesced_runs == 1 and net.fanout_msgs == 3
    sim.run()
    assert [e[0] for e in log] == ["p1", "p2", "p3"]
    assert len({e[3] for e in log}) == 1      # one shared arrival timestamp


def test_send_fanout_falls_back_on_link_state():
    sim, net, log = _fanout_rig(0.0)
    net.partition("p0", "p1", forced=True)
    net.send_fanout("p0", ["p1", "p2"], "hi", 10)
    assert net.fanout_msgs == 0               # scalar fallback path
    sim.run()
    assert [e[0] for e in log] == ["p2"]


# ------------------------------------------------------ end-to-end proof
def test_cluster_stats_expose_hot_batch_counters():
    from repro.apps.flip import FlipApp
    from repro.core.consensus import ConsensusConfig
    from repro.core.smr import build_cluster
    crypto.reset_digest_stats()
    c = build_cluster(FlipApp, cfg=ConsensusConfig(max_batch=4,
                                                   pipeline_depth=2))
    cl = c.new_client()
    done = {"n": 0}

    # enough slots to cross a certify-summary window (t/2 slots) — that is
    # where the batched fingerprint path runs
    target = c.replicas[0].cfg.t // 2 + 8

    def cb(_res, _lat):
        done["n"] += 1
        if done["n"] < target:
            cl.request(b"x" * 16, cb)

    cl.request(b"x" * 16, cb)
    assert c.sim.run_until(lambda: done["n"] >= target, timeout=1_000_000.0)
    eng = c.stats()["engine"]
    assert eng["net"]["fanout_msgs"] > 0
    assert eng["net"]["msgs_sent"] >= eng["net"]["fanout_msgs"]
    assert eng["digests"]["batch_fingerprint_items"] > 0
    assert eng["digests"]["wire_cache_hits"] > 0
    assert eng["events_processed"] == c.sim.events_processed > 0


# ------------------------------------------------- hypothesis deep sweep
def test_property_batch_digests_match_scalar():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.binary(max_size=300), max_size=12),
           st.sampled_from(["hashlib", "numpy"]))
    def check(datas, backend):
        assert crypto.fingerprint_batch(datas, backend=backend) == \
            [crypto.fingerprint(d) for d in datas]
        assert crypto.checksum_batch(datas) == \
            [crypto.checksum(d) for d in datas]

    check()


def test_property_verify_batch_matches_scalar():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    reg = crypto.KeyRegistry()
    signer = reg.keygen("q")

    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.tuples(st.binary(max_size=40), st.booleans()),
                    max_size=8))
    def check(specs):
        items = []
        for payload, valid in specs:
            sig = signer.sign(payload) if valid else b"\x00" * 16
            items.append(("q", payload, sig))
        assert reg.verify_batch(items) == \
            [reg.verify(pid, p, s) for pid, p, s in items]

    check()
