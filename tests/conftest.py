import os
import sys

import pytest

# Tests and benches see ONE device; the mesh tests run in subprocesses that
# set their own XLA_FLAGS for virtual CPU devices before any jax import.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def pooled_cluster():
    """Factory for a kvstore uBFT cluster over sharded memory pools —
    the shared rig for the fault-schedule matrix.  Built through the
    Substrate/attach API (a private substrate, one unnamed app — the pids
    match the legacy ``build_cluster`` layout: r0.., m0.., p1m0..)."""
    from repro.apps.kvstore import KVStoreApp
    from repro.core.consensus import ConsensusConfig
    from repro.core.smr import Cluster
    from repro.core.substrate import Substrate

    def make(n_pools=2, f=None, f_m=None, seed=0, cfg=None, **kw):
        if cfg is not None:
            # mirror build_cluster: never silently clobber a caller cfg
            if f is not None and f != cfg.f:
                raise ValueError(f"conflicting f={f} vs cfg.f={cfg.f}")
            if f_m is not None and f_m != cfg.f_m:
                raise ValueError(f"conflicting f_m={f_m} vs "
                                 f"cfg.f_m={cfg.f_m}")
        else:
            cfg = ConsensusConfig(f=1 if f is None else f,
                                  f_m=1 if f_m is None else f_m)
        substrate = Substrate(f_m=cfg.f_m, n_pools=n_pools, seed=seed, **kw)
        return Cluster.attach(substrate, KVStoreApp, name="", cfg=cfg)

    return make


@pytest.fixture
def shared_substrate():
    """Factory for a multi-application deployment: one substrate, N named
    kvstore apps attached to the same pools (the cross-app isolation rig)."""
    from repro.apps.kvstore import KVStoreApp
    from repro.core.smr import Cluster
    from repro.core.substrate import Substrate

    def make(app_names, n_pools=2, f_m=1, seed=0, cfg_fn=None,
             app_factory=KVStoreApp, **kw):
        substrate = Substrate(f_m=f_m, n_pools=n_pools, seed=seed, **kw)
        clusters = {
            name: Cluster.attach(substrate, app_factory, name=name,
                                 cfg=cfg_fn() if cfg_fn else None)
            for name in app_names
        }
        return substrate, clusters

    return make


@pytest.fixture
def fault_injector():
    """Factory wiring a FaultInjector (with pool resolution) to a cluster."""
    from repro.sim.faults import FaultInjector

    def make(cluster, schedule=None):
        return FaultInjector.for_cluster(cluster, schedule)

    return make
