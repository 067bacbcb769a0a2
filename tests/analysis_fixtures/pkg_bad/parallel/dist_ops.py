"""Fixture distributed ops for the span/ledger-coverage checkers: one
fully instrumented op (clean), one bare op (seeded for BOTH families),
one spanned-but-untracked op (seeded for ledger-coverage only), plus a
private helper and a non-distributed public function — both outside the
contracts."""
from ..telemetry import ledger as _ledger, phase as _phase


def distributed_spanned(t):
    with _phase("distributed_spanned.work", 0):
        return _ledger.track(t, "distributed_spanned")


def distributed_bare(t):  # SEEDED: span-coverage + ledger-coverage
    return t + 1


def distributed_untracked(t):  # SEEDED: ledger-coverage/missing-ledger
    with _phase("distributed_untracked.work", 0):
        return t


def _helper(t):  # private: outside the contract
    return t


def repartition_like(t):  # public but not distributed_*: outside
    return t


def _rogue_kernel_fn(mesh):  # SEEDED: collectives/uncataloged-factory
    return mesh


def _host_helper_fn(axis):  # cylint: disable=collectives/uncataloged-factory
    # intentional exclusion: plain host callable, not a jitted program
    return lambda x: x


def _chunk_rogue_fn(mesh, block, chunk_block):  # SEEDED: collectives/uncataloged-factory (chunked-path control)
    return mesh


def _partition_rogue_fn(mesh, block, part):  # SEEDED: collectives/uncataloged-factory (partition-path control)
    return mesh


def _bcast_rogue_fn(mesh, join_type):  # SEEDED: collectives/uncataloged-factory (broadcast-path control)
    return mesh


def _count_bare(x):
    import jax

    def fetch():
        return jax.device_get(x)   # SEEDED: hostsync/bare-fetch (once)
    return fetch()


def _gather_for_output(x):  # declared in the test's bulk_exports: clean
    import jax

    return jax.device_get(x)


def _count_choked(x, site):
    from ..telemetry import host_fetch as _host_fetch

    n = _host_fetch("fixture.count", x)    # literal site: clean
    return n + _host_fetch(site, x)  # SEEDED: span-coverage/dynamic-sync-site
