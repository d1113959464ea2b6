"""The benchmark's arithmetic, discovery by name, work functions, data
generators and its refusal to run without a TPU."""

import json
import os
import re
import shutil
import statistics
import subprocess
import sys

import numpy as np
import pytest

from harness import registry, stats
from harness.record import Record
from harness.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# -- arithmetic ------------------------------------------------------------


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(q).exponential(size=101)) + [3.0, 3.0]
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)), rel=1e-12)


def test_spread_is_interquartile_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


def test_union_covered_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (9.0, 12.0)]
    assert stats.union(iv, 0.0, 10.0) == [(0.0, 2.0), (3.0, 4.0), (9.0, 10.0)]
    assert stats.covered(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert stats.gaps(iv, -1.0, 10.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 9.0)]
    assert stats.covered([], 0.0, 1.0) == 0.0


def test_trace_reduction_by_hand():
    # two devices: device 0 busy [0,1] and [2,3]; device 1 busy [0,4]
    ops = [(0, "fusion", 0.0, 1.0), (0, "sort", 2.0, 3.0), (1, "fusion", 0.0, 4.0)]
    spans = [("bench.call", 0.0, 1.5), ("bench.client", 1.5, 2.5)]
    tr = Trace(ops, spans, (0.0, 4.0))
    assert tr.busy_s() == pytest.approx((2.0 + 4.0) / 2)
    assert tr.idle_share() == pytest.approx(25.0)
    assert tr.top_ops() == [["fusion", 2.5], ["sort", 0.5]]
    # device 0 idles in [1, 2] (host in bench.client, the innermost span at
    # 1.5) and in [3, 4] (no span)
    assert tr.idle_gaps() == [["bench.client", 1.0], ["outside bench spans", 1.0]]
    assert tr.label(0.7) == "bench.call"


def test_reduction_of_a_recorded_chip_trace():
    """A recorded TPU v5e trace: 0.6 s of the merge cell at TPC-H SF1
    (merge_kv of 1,500,000 + 6,001,215 rows), reduced here and by a plain
    sweep."""
    tr = Trace.from_json(os.path.join(HERE, "testdata", "merge_kv_trace.json.gz"))
    lo, hi = tr.window
    assert tr.devices == [0] and hi - lo == pytest.approx(0.6)
    # busy time by a sweep over sorted start/end events
    events = sorted([(max(s, lo), 1) for _, _, s, e in tr.ops if e > lo and s < hi]
                    + [(min(e, hi), -1) for _, _, s, e in tr.ops if e > lo and s < hi])
    busy, depth, last = 0.0, 0, lo
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    assert tr.busy_s() == pytest.approx(busy, rel=1e-9)
    assert 0.0 < tr.idle_share() < 20.0
    top = tr.top_ops()
    assert top[0][0] == "jit__merge_kv_launch/%_merge_kv_launch.1" and top[0][1] > 0.8 * busy
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = tr.idle_gaps()
    assert gaps and all(label in ("bench.call", "outside bench spans") for label, _ in gaps)
    assert sum(g for _, g in gaps) <= tr.window_s - busy + 1e-9
    cfg = registry.config("tpch-sf10-orderkey")
    calls = [(s, e, 7_501_215) for s, e in tr.span_intervals("bench.call")]
    rec = Record(kind="library", cell={"config": cfg, "traffic": {"op": "merge_kv"}},
                 peaks={"hbm_bytes_per_s": 819e9}, work=registry.work("tpch-sf10-orderkey"),
                 setup_s=0.0, window=tr.window, calls=calls, trace=tr)
    share = registry.reader("merge_kv_roofline").read(rec)
    whole = [(s, e) for s, e in tr.span_intervals("bench.call") if s >= lo and e <= hi]
    want = 100 * len(whole) * 16 * 7_501_215 / 819e9 / sum(tr.busy_s(s, e) for s, e in whole)
    assert share == pytest.approx(want) and 0 < share < 100


# -- work functions --------------------------------------------------------


def test_phi_flops_per_token():
    w = registry.work("phi3.5-moe-4l")
    cfg = registry.config("phi3.5-moe-4l")
    assert w.flops_per_token(cfg, 0) == 2 * w.active_params(cfg)
    # 4 layers of 199.3e6 (attention 41.9e6, two experts 157.3e6, router) and the LM head 131.3e6
    assert w.flops_per_token(cfg, 0) == pytest.approx(1.857e9, rel=0.001)
    # attention over 1,000 positions: 4 layers x 4 x 32 heads x 128
    assert w.flops_per_token(cfg, 1000) - w.flops_per_token(cfg, 0) == 4 * 4 * 32 * 128 * 1000


@pytest.mark.parametrize("op", ["sort_kv", "merge_kv"])
def test_tpch_least_bytes_are_16_per_row(op):
    w = registry.work("tpch-sf10-orderkey")
    assert w.least_bytes(registry.config("tpch-sf10-orderkey"), op, 1000) == 16 * 1000


# -- discovery by name -----------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = registry.cell(name, BENCH)
    tr = cell["traffic"]
    kind = registry.kind(tr["kind"])
    assert callable(kind.run) and callable(kind.control)
    if tr["kind"] == "library":
        op = registry.op(tr["op"])
        assert callable(op.reference) and callable(op.control)
        assert callable(registry.reference(cell["config_name"]).tables)
    else:
        assert callable(registry.reference(cell["config_name"]).logits)
    registry.work(cell["config_name"])
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["end_to_end"] + cell["per_layer"]:
        assert callable(registry.reader(m["name"]).read)
    for m in cell["per_layer"]:
        assert m["moves"] in e2e
    assert set(tr["check"]) and all(isinstance(v, (int, float)) for v in tr["check"].values())


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/") and os.path.isfile(os.path.join(registry.CHECKOUT, c["file"]))
        cfg = registry.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(BENCH)) < 64 * 1024


# -- generators ------------------------------------------------------------


def test_tpch_sf10_config_holds_the_spec_counts():
    cfg = registry.config("tpch-sf10-orderkey")
    assert cfg["scale_factor"] == 10
    assert cfg["orders_rows"] == 15_000_000 and cfg["lineitem_rows"] == 59_986_052
    last = (cfg["orders_rows"] - 1) // cfg["orderkey_used"] * cfg["orderkey_stride"] + (cfg["orders_rows"] - 1) % 8 + 1
    assert last == 59_999_976 and last < 1 << 30  # keys and tagged row ids fit int32


def test_tpch_tables_from_the_seed():
    """The generator at SF1's published counts (the SF10 tables are ten
    times as large, too large for a test run)."""
    import jax

    ref = registry.reference("tpch-sf10-orderkey")
    cfg = dict(registry.config("tpch-sf10-orderkey"), orders_rows=1_500_000, lineitem_rows=6_001_215)
    from harness import seeds

    t1 = ref.tables(cfg, seeds.jax_key(2**33 + 1, 1, 0))
    t2 = ref.tables(cfg, seeds.jax_key(2**33 + 1, 1, 0))
    t3 = ref.tables(cfg, seeds.jax_key(1, 1, 0))
    ok, oi = (np.asarray(a) for a in t1["orders"])
    lk, li = (np.asarray(a) for a in t1["lineitem"])
    counts = np.asarray(t1["counts"])
    assert ok.shape == (1_500_000,) and lk.shape == (6_001_215,)
    assert counts.sum() == 6_001_215 and counts.min() >= 1 and counts.max() <= 7
    assert ok[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33] and ok[-1] == 5_999_976
    assert np.all(np.diff(lk) >= 0) and np.array_equal(np.unique(lk), ok)
    assert np.array_equal(np.bincount(np.searchsorted(ok, lk), minlength=ok.size), counts)
    assert np.array_equal(oi, np.arange(ok.size)) and np.array_equal(li, np.arange(lk.size))
    assert np.array_equal(lk, np.asarray(t2["lineitem"][0]))  # same seed, same tables
    assert not np.array_equal(counts, np.asarray(t3["counts"]))  # another seed, other counts
    jax.clear_caches()


@pytest.mark.parametrize("traffic", ["decode-closed8"])
def test_every_seed_gets_the_same_request_sizes(traffic):
    plan = registry.kind("serving").plan
    tr = registry.traffic(traffic)
    n = tr["pool"]

    def stream(seed):
        return [r for _, r in zip(range(n), plan(tr, seed, 32064))]

    a, b = stream(7), stream(2**33 + 7)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]  # the seed orders them
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])  # and draws the tokens
    for i in range(0, n, 8):  # block by block, the same (prompt, output) sizes
        assert sorted((len(r["prompt"]), r["max_new"]) for r in a[i:i + 8]) == \
            sorted((len(r["prompt"]), r["max_new"]) for r in b[i:i + 8])
    for r in a:
        assert tr["prompt"]["min"] <= len(r["prompt"]) <= tr["prompt"]["max"]
        assert tr["output"]["min"] <= r["max_new"] <= tr["output"]["max"]
        assert len(r["prompt"]) + r["max_new"] < tr["max_seq"]
    # every block of 8 takes one prompt from each eighth of the distribution
    lengths = sorted(len(r["prompt"]) for r in a)
    for i in range(0, n, 8):
        block = sorted(len(r["prompt"]) for r in a[i:i + 8])
        assert all(lengths[(n // 8) * s] <= x <= lengths[(n // 8) * (s + 1) - 1] for s, x in enumerate(block))
    assert abs(statistics.median(lengths) - tr["prompt"]["median"]) <= 0.1 * tr["prompt"]["median"]


# -- readers ---------------------------------------------------------------


def _serving_record():
    cfg = registry.config("phi3.5-moe-4l")
    reqs = [
        {"submit": 0.0, "times": [1.0, 1.0, 1.5, 2.0], "prompt": [1] * 4},
        {"submit": 0.5, "times": [2.0, 2.0, 2.5], "prompt": [1] * 2},
        {"submit": 3.5, "times": [4.5], "prompt": [1] * 2},
    ]
    steps = [
        {"start": 0.0, "end": 1.0, "tokens": 2, "first_token": True, "positions": [0, 1, 2, 3, 4]},
        {"start": 1.0, "end": 1.5, "tokens": 1, "first_token": False, "positions": [5]},
        {"start": 1.5, "end": 2.0, "tokens": 3, "first_token": True, "positions": [6, 0, 1, 2]},
        {"start": 2.0, "end": 2.5, "tokens": 1, "first_token": False, "positions": [3]},
    ]
    return Record(kind="serving", cell={"config": cfg}, peaks={"bf16_flops_per_s": 197e12},
                  work=registry.work("phi3.5-moe-4l"), setup_s=7.0, window=(1.0, 3.0),
                  requests=reqs, steps=steps)


def test_serving_readers_by_hand():
    rec = _serving_record()
    read = lambda m: registry.reader(m).read(rec)  # noqa: E731
    assert read("setup_s") == 7.0
    assert read("output_tokens_per_s") == pytest.approx((1 + 3 + 1) / 2.0)
    w, cfg = rec.work, rec.cell["config"]
    flops = sum(w.flops_per_token(cfg, p + 1) for p in [5, 6, 0, 1, 2, 3])
    assert read("mfu.serve") == pytest.approx(100 * flops / 2.0 / 197e12)
    assert read("keys_per_s") is None and read("sort_kv_roofline") is None


def test_library_readers_by_hand():
    cfg = registry.config("tpch-sf10-orderkey")
    ops = [(0, "sort", 0.1, 0.3), (0, "merge", 0.3, 0.4), (0, "sort", 1.1, 1.2)]
    spans = [("bench.call", 0.0, 0.5), ("bench.call", 0.5, 1.0), ("bench.call", 1.0, 1.5)]
    rec = Record(kind="library", cell={"config": cfg, "traffic": {"op": "sort_kv"}},
                 peaks={"hbm_bytes_per_s": 819e9}, work=registry.work("tpch-sf10-orderkey"),
                 setup_s=3.0, window=(0.0, 1.5), calls=[(0.0, 0.5, 100), (0.5, 1.0, 100), (1.0, 1.5, 100)],
                 trace=Trace(ops, spans, (0.0, 1.5)))
    read = lambda m: registry.reader(m).read(rec)  # noqa: E731
    assert read("keys_per_s") == pytest.approx(300 / 1.5)
    assert read("sort_kv_roofline") == pytest.approx(100 * 3 * 1600 / 819e9 / 0.4)
    assert read("merge_kv_roofline") is None
    assert read("device_idle_share.keys") == pytest.approx(100 * (1 - 0.4 / 1.5))
    assert read("device_idle_share.serve") is None and read("output_tokens_per_s") is None


# -- refusals --------------------------------------------------------------


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_run_refuses_a_cpu_backend():
    p = _run(registry.CHECKOUT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(registry.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_has_no_peaks():
    from harness import device

    with pytest.raises(SystemExit):
        device.peaks("TPU v99")
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
