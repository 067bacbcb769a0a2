"""How a test reaches a distributed path the code would not choose for
its inputs. No path under `parallel/` or `plan/` is chosen by a knob
(PR 45): each is a pure function of what the code observes, so a test
that wants the other one patches that function for its own duration
(`scripts/fuzz_differential.py` does the same, through these helpers).
"""
from cylon_tpu.ops import tpu_kernels as _tk
from cylon_tpu.parallel import shuffle as _shuffle
from cylon_tpu.plan import optimizer as _optimizer
from cylon_tpu.util import pow2_floor as _pow2_floor

_chunk_plan = _shuffle._chunk_plan
_broadcast_choice = _optimizer.broadcast_choice
_salt_choice = _optimizer.salt_choice


def partition(monkeypatch, part: str) -> None:
    """``"sort"``: the XLA stable sort everywhere. ``"pallas"``: the
    kernel wherever a payload is eligible for it, under the interpreter
    off a TPU."""
    def kernel(mesh, world, payload):
        if world < 2 or world + 1 > _tk.LANES \
                or not _shuffle._partition_eligible(payload):
            return "sort"
        on_tpu = mesh.devices.flat[0].platform == "tpu"
        return "pallas" if on_tpu else "interp"

    path = {"sort": lambda mesh, world, payload: "sort",
            "pallas": kernel}[part]
    monkeypatch.setattr(_shuffle, "_partition_path", path)


def single_shot(monkeypatch, on: bool = True) -> None:
    """The exchange as ONE program whatever the pool's budget (``on``),
    or chunked as the budget decides."""
    monkeypatch.setattr(
        _shuffle, "_chunk_plan",
        (lambda block, *_a, **_k: (block, 1)) if on else _chunk_plan)


def chunked(monkeypatch, chunk_bytes: int) -> None:
    """The exchange chunked at ``chunk_bytes`` of payload a chunk and a
    shard, whatever the pool's budget: the arithmetic by which a
    constant of bytes decided before PR 48 (64 MiB on the chip, 4096 in
    the tests), so that the chunk pipeline runs at a test's size."""
    def plan(block, world, bytes_per_row, *_a, **_k):
        per_slot = max(int(bytes_per_row), 1) * max(world, 1)
        return _shuffle._chunks_of(
            block, _pow2_floor(max(chunk_bytes // per_slot, 1)))

    monkeypatch.setattr(_shuffle, "_chunk_plan", plan)


def shuffle_joins_only(monkeypatch, on: bool = True) -> None:
    """No adaptive rewrite: every join stays a shuffle join and no
    exchange is salted, whatever the statistics warehouse holds. The
    plan cache is emptied: a template there was chosen by the other
    functions, and its staleness check sees knobs and statistics."""
    from cylon_tpu.service import plancache

    plancache.global_cache().clear()
    monkeypatch.setattr(_optimizer, "broadcast_choice",
                        (lambda node, world: None) if on
                        else _broadcast_choice)
    monkeypatch.setattr(_optimizer, "salt_choice",
                        (lambda node, world: False) if on
                        else _salt_choice)
