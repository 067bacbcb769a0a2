"""Randomized differential testing: random tables (mixed dtypes,
strings in both storages, nulls), random relational ops — every result
checked three ways: distributed (8-device virtual mesh) vs local vs
pandas. Seeded per case; a failure prints the reproducing seed.

Each case additionally generates a random **LazyTable plan** (scan →
optional filter → join → optional groupby / standalone shuffle) and
differentially tests the OPTIMIZED execution against the unoptimized
plan and pandas, with the join's algorithm varied per case (auto /
no adaptive rewrite / a join written as algorithm="broadcast";
CYLON_SALT_FACTOR ∈ 0/4) and the warehouse pre-learned for the auto
cases — randomized
evidence per optimizer rule, broadcast/salt rewrites included
(ROADMAP item 5).

Usage: python scripts/fuzz_differential.py [n_cases=40] [base_seed=0]
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

import pandas as pd  # noqa: E402

import cylon_tpu as ct  # noqa: E402
from cylon_tpu.data import strings as _strings  # noqa: E402

# No knob chooses a distributed path (PR 45): the fuzzer reaches the
# paths the code would not choose on the CPU as the tests do, by
# standing in for the functions that choose (tests/forced_paths.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
import forced_paths  # noqa: E402
from _pytest.monkeypatch import MonkeyPatch  # noqa: E402


def rand_keys(rng, n, kind):
    if kind == "int32":
        return rng.integers(-50, 50, n).astype(np.int32)
    if kind == "int64":
        return rng.integers(-1000, 1000, n).astype(np.int64)
    if kind == "short_str":
        return np.array([f"k{int(x):03d}" for x in
                         rng.integers(0, 60, n)], object)
    if kind == "long_str":
        return np.array([f"{'L' * 30}{int(x):04d}" for x in
                         rng.integers(0, 60, n)], object)
    raise AssertionError(kind)


def rand_table(rng, n, kind, extra):
    d = {"k": rand_keys(rng, n, kind),
         extra: rng.normal(size=n).astype(np.float32)}
    return d


def canon(df):
    df = df.copy()
    df.columns = range(len(df.columns))
    rows = []
    for t in df.itertuples(index=False):
        # stringify EVERY cell so mixed null/str/float columns sort
        rows.append(tuple(
            "<null>" if v is None or v != v else
            (f"{float(v):.3f}" if isinstance(v, (float, np.floating))
             else str(v)) for v in t))
    return sorted(rows)


def one_case(seed):
    rng = np.random.default_rng(seed)
    kind = rng.choice(["int32", "int64", "short_str", "long_str"])
    n1 = int(rng.integers(8, 400))
    n2 = int(rng.integers(8, 400))
    jt = rng.choice(["inner", "left", "right", "outer"])
    force_vb = bool(rng.integers(0, 2)) and "str" in kind
    with_nulls = bool(rng.integers(0, 2)) and "str" in kind

    # randomly toggle the chunked exchange: with a tiny chunk target
    # (forced: no budget asks for one) every padded exchange runs the
    # chunked pipeline,
    # which must stay bit-identical to the single-shot program on all
    # of the distributed-vs-local comparisons below
    patch = MonkeyPatch()
    overlap = bool(rng.integers(0, 2))
    if overlap:
        forced_paths.chunked(patch, 4096)
    else:
        forced_paths.single_shot(patch)
    # …and, orthogonally, the partition path: "pallas" runs the fused
    # hash+bucket+scatter kernel under the Pallas interpreter on CPU,
    # "sort" the XLA stable sort — differential evidence across the
    # full matrix (overlap × partition), every combination must
    # agree with local AND pandas
    partition = "pallas" if bool(rng.integers(0, 2)) else "sort"
    forced_paths.partition(patch, partition)

    old = _strings.DICT_MAX_VOCAB
    if force_vb:
        _strings.DICT_MAX_VOCAB = 0
    try:
        ld = rand_table(rng, n1, kind, "v")
        rd = rand_table(rng, n2, kind, "w")
        if with_nulls:
            ld["k"][rng.integers(0, n1, max(n1 // 10, 1))] = None
            rd["k"][rng.integers(0, n2, max(n2 // 10, 1))] = None
        dctx = ct.CylonContext.InitDistributed(ct.TPUConfig())
        lctx = ct.CylonContext.Init()

        lt_d = ct.Table.from_pydict(dctx, ld)
        rt_d = ct.Table.from_pydict(dctx, rd)
        lt_l = ct.Table.from_pydict(lctx, ld)
        rt_l = ct.Table.from_pydict(lctx, rd)

        jd = lt_d.distributed_join(rt_d, jt, on="k").to_pandas()
        jl = lt_l.join(rt_l, jt, on="k").to_pandas()
        assert canon(jd) == canon(jl), f"dist!=local join seed={seed}"
        if not with_nulls:
            # null-key match semantics differ from pandas (pandas merges
            # NaN keys as equal) — pandas row counts only on clean keys
            how = {"inner": "inner", "left": "left", "right": "right",
                   "outer": "outer"}[jt]
            jp = pd.DataFrame(ld).merge(pd.DataFrame(rd), on="k",
                                        how=how)
            assert len(jd) == len(jp), \
                f"rowcount vs pandas seed={seed}: {len(jd)} != {len(jp)}"

        # set ops: distributed vs local (schemas must match: k only)
        sld = ct.Table.from_pydict(dctx, {"k": ld["k"]})
        srd = ct.Table.from_pydict(dctx, {"k": rd["k"]})
        sll = ct.Table.from_pydict(lctx, {"k": ld["k"]})
        srl = ct.Table.from_pydict(lctx, {"k": rd["k"]})
        for op in ("union", "intersect", "subtract"):
            ud = getattr(sld, f"distributed_{op}")(srd).to_pandas()
            ul = getattr(sll, op)(srl).to_pandas()
            assert canon(ud) == canon(ul), \
                f"dist!=local {op} seed={seed}"

        # groupby sum/count on the left table
        gd = lt_d.groupby(0, [1, 1], ["sum", "count"]).to_pandas()
        gl = lt_l.groupby(0, [1, 1], ["sum", "count"]).to_pandas()
        # dropna=False: null keys form ONE group here (Arrow/SQL GROUP
        # BY semantics), which pandas only matches with dropna=False
        gp = pd.DataFrame(ld).groupby("k", dropna=False)["v"].agg(
            ["sum", "count"])
        assert len(gd) == len(gl) == len(gp), f"groupby len seed={seed}"
        a = gd.sort_values(gd.columns[0]).reset_index(drop=True)
        b = gl.sort_values(gl.columns[0]).reset_index(drop=True)
        np.testing.assert_allclose(
            a.iloc[:, 1].astype(float), b.iloc[:, 1].astype(float),
            rtol=1e-4, err_msg=f"groupby sum seed={seed}")

        # distributed sort (fixed-width and short strings sort on
        # device; long strings take the host path)
        sd = ct.distributed_sort(lt_d, "k")
        sl = lt_l.sort("k")
        kd = [x for x in sd.to_pydict()["k"].tolist()]
        kl = [x for x in sl.to_pydict()["k"].tolist()]
        assert kd == kl, f"sort seed={seed}"
    finally:
        _strings.DICT_MAX_VOCAB = old
        patch.undo()
    return kind, jt, force_vb, overlap, partition


def lazy_plan_case(seed):
    """One random LazyTable plan, differentially tested optimized vs
    unoptimized vs pandas under a randomized join algorithm: what the
    statistics decide ("auto"), no adaptive rewrite at all ("shuffle")
    or a join the plan itself writes as algorithm="broadcast"."""
    import pandas as pd

    from cylon_tpu import plan as ct_plan
    from cylon_tpu.telemetry import stats as stats_mod

    rng = np.random.default_rng(seed ^ 0x5A17)
    kind = rng.choice(["int32", "int64", "short_str"])
    n1 = int(rng.integers(64, 600))
    n2 = int(rng.integers(8, 200))
    jt = rng.choice(["inner", "left", "right"])
    mode = rng.choice(["auto", "shuffle", "broadcast"])
    salt = int(rng.choice([0, 4]))
    zipf = bool(rng.integers(0, 2))
    with_gb = bool(rng.integers(0, 2)) and kind != "short_str"
    with_shuffle = bool(rng.integers(0, 2))
    patch = MonkeyPatch()
    if mode == "shuffle":
        forced_paths.shuffle_joins_only(patch)
    patch.setenv("CYLON_SALT_FACTOR", str(salt))
    patch.setenv("CYLON_STATS_MIN_OBS", "2")
    stats_mod.reset()
    try:
        ld = rand_table(rng, n1, kind, "v")
        rd = rand_table(rng, n2, kind, "w")
        if zipf and kind == "int32":
            hot = ld["k"][0]
            ld["k"] = np.where(rng.random(n1) < 0.6, hot,
                               ld["k"]).astype(np.int32)
        dctx = ct.CylonContext.InitDistributed(ct.TPUConfig())
        lt_d = ct.Table.from_pydict(dctx, ld)
        rt_d = ct.Table.from_pydict(dctx, rd)

        def pipe():
            lt = ct_plan.scan(lt_d)
            if with_shuffle:
                lt = lt.shuffle(["k"])
            p = lt.join(ct_plan.scan(rt_d), jt, on="k",
                        algorithm="broadcast" if mode == "broadcast"
                        else "auto")
            if with_gb:
                # aggregate_cols pairs 1:1 with ops (the eager groupby
                # call shape above)
                p = p.groupby("lt-0", ["rt-3", "rt-3"],
                              ["sum", "count"])
            return p

        ref = pipe().execute(optimize=False).to_pandas()
        # repeated optimized executions: the auto cases LEARN across
        # runs (run 1-2 exploratory shuffle, run 3 may rewrite) —
        # every run must match the unoptimized plan bit for bit
        for run in range(3):
            got = pipe().execute().to_pandas()
            assert canon(got) == canon(ref), \
                f"lazy plan optimized!=unoptimized seed={seed} " \
                f"run={run} mode={mode} salt={salt}"
        if not with_gb:
            how = {"inner": "inner", "left": "left",
                   "right": "right"}[jt]
            jp = pd.DataFrame(ld).merge(pd.DataFrame(rd), on="k",
                                        how=how)
            assert len(ref) == len(jp), \
                f"lazy plan rowcount vs pandas seed={seed}: " \
                f"{len(ref)} != {len(jp)}"
    finally:
        patch.undo()
        stats_mod.reset()
    return jt, mode, salt, with_gb, with_shuffle


def main(n_cases, base):
    bad = 0
    for i in range(n_cases):
        seed = base + i
        try:
            kind, jt, fv, ov, pk = one_case(seed)
            print(f"case {seed}: ok ({kind}, {jt}, vb={fv}, part={pk}, "
                  f"overlap={ov})", flush=True)
        except AssertionError as e:
            bad += 1
            print(f"case {seed}: FAIL {e}", flush=True)
        except Exception as e:
            bad += 1
            print(f"case {seed}: ERROR {type(e).__name__}: {e}",
                  flush=True)
        try:
            jt, mode, salt, gb, sh = lazy_plan_case(seed)
            print(f"plan case {seed}: ok ({jt}, algo={mode}, "
                  f"salt={salt}, groupby={gb}, shuffle={sh})",
                  flush=True)
        except AssertionError as e:
            bad += 1
            print(f"plan case {seed}: FAIL {e}", flush=True)
        except Exception as e:
            bad += 1
            print(f"plan case {seed}: ERROR {type(e).__name__}: {e}",
                  flush=True)
    print(f"{n_cases - bad}/{n_cases} passed")
    return bad


def chunked(n, base, chunk=12):
    """Fresh interpreter per chunk: one process accumulates jit code
    until LLVM hits 'Cannot allocate memory' after ~20 random-shape
    cases — an artifact of compile churn no real pipeline reproduces."""
    import subprocess

    bad = 0
    here = os.path.abspath(__file__)
    for lo in range(0, n, chunk):
        c = min(chunk, n - lo)
        p = subprocess.run(
            [sys.executable, here, str(c), str(base + lo), "--one-shot"],
            capture_output=True, text=True)
        sys.stdout.write(p.stdout)
        if p.returncode != 0:
            bad += 1
    return bad


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--one-shot"]
    n = int(args[0]) if args else 40
    b = int(args[1]) if len(args) > 1 else 0
    if "--one-shot" in sys.argv or n <= 12:
        sys.exit(1 if main(n, b) else 0)
    sys.exit(1 if chunked(n, b) else 0)
