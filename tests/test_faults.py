"""Fault-injection sweep: drive every guarded fallback edge deterministically.

Each fault class (``launch``, ``vmem``, ``exchange``, ``nan``) is forced
through :mod:`repro.runtime.faults` and the degraded output is asserted
bit-identical to the clean chain or a NumPy/total-order oracle — the
self-healing layer's contract is that a fallback changes *where* the
answer is computed, never the answer.

Also covers the serving engine's graceful degradation (deadlines,
load-shedding, bounded retry, partial-result surfacing) and the
multi-device distributed chains (subprocess, 8 fake CPU devices).

Pure pytest — no hypothesis — so the whole file is tier-1 in offline
containers.  ``make test-faults`` runs it twice: once clean, once under
an env-driven ``REPRO_FAULTS`` plan (the ``env_plan`` test).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import merge_path as mp
from repro.kernels import ops
from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_ref
from repro.runtime import faults
from repro.runtime import resilience as res
from repro.runtime.resilience import FallbackWarning, GuardedDispatchError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every injected fault emits a FallbackWarning by design; individual tests
# assert on it explicitly with pytest.warns where the message matters
pytestmark = pytest.mark.filterwarnings(
    "ignore::repro.runtime.resilience.FallbackWarning"
)


@pytest.fixture(autouse=True)
def _clean_state():
    faults.reset_counters()
    res.reset_health()
    yield
    faults.reset_counters()
    res.reset_health()


def _tok_np(x) -> np.ndarray:
    return np.asarray(mp.total_order_keys(jnp.asarray(x)))


def _tok_stable_sort(x: np.ndarray) -> np.ndarray:
    """NumPy oracle for the repo's total-order (NaN-last) stable sort."""
    order = np.argsort(_tok_np(x), kind="stable")
    return x[order]


def _tree_equal(a, b) -> None:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        if np.issubdtype(x.dtype, np.floating):
            assert np.array_equal(x, y, equal_nan=True)
        else:
            assert np.array_equal(x, y)


# ---------------------------------------------------------------------------
# injector unit tests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kind,row", [("TPU v5 lite", "tpu-v5e"), ("TPU v4", "tpu-v4"), ("TPU v9 imaginary", None)]
)
def test_budget_follows_attached_tpu_kind(monkeypatch, kind, row):
    """Preflight budgets the attached TPU, and refuses a kind it cannot map."""
    from repro.analysis.checker import VMEM_BUDGET_BYTES, VMEM_USABLE_FRACTION

    class _Dev:
        device_kind = kind

    monkeypatch.delenv("REPRO_GUARD_DEVICE", raising=False)
    monkeypatch.setattr(res.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(res.jax, "devices", lambda: [_Dev()])
    if row is None:
        with pytest.raises(ValueError, match="TPU kind"):
            res._budget_bytes()
    else:
        assert res._budget_bytes() == int(VMEM_BUDGET_BYTES[row] * VMEM_USABLE_FRACTION)


def test_plan_grammar():
    specs = faults.parse_plan(
        "launch:merge:0,2; nan:*:*; exchange:distributed_merge:1:window; vmem:sort*"
    )
    assert [s.cls for s in specs] == ["launch", "nan", "exchange", "vmem"]
    assert specs[0].op == "merge" and specs[0].indices == (0, 2)
    assert specs[1].op == "*" and specs[1].indices is None
    assert specs[2].indices == (1,) and specs[2].match == "window"
    assert specs[3].op == "sort*" and specs[3].indices is None
    with pytest.raises(ValueError):
        faults.parse_plan("explode:merge:0")
    with pytest.raises(ValueError):
        faults.parse_plan("launch")
    with pytest.raises(ValueError):
        faults.parse_plan("launch:merge:0:pallas:extra")


def test_should_fire_semantics():
    with faults.inject("launch:merge:*"):
        # a spec without a match never fires on the last attempt of a chain
        assert faults.should_fire("launch", "merge", 0, label="pallas-hier", last=False)
        assert not faults.should_fire("launch", "merge", 0, label="core", last=True)
        assert not faults.should_fire("launch", "sort", 0, label="pallas-hier")
    with faults.inject("launch:sort:1:pallas"):
        # an explicit match is a substring filter and ignores `last`
        assert not faults.should_fire("launch", "sort", 0, label="pallas-hier")
        assert faults.should_fire("launch", "sort", 1, label="pallas-hier", last=True)
        assert not faults.should_fire("launch", "sort", 1, label="core", last=True)
    assert not faults.should_fire("launch", "merge", 0, label="pallas-hier")


def test_inject_stacks_and_restores_counters():
    assert not faults.active() or os.environ.get("REPRO_FAULTS")
    base = faults.next_index("merge")
    with faults.inject("launch:merge:*"):
        assert faults.active()
        assert faults.next_index("merge") == 0  # counters snapshot to zero
        assert faults.should_fire("launch", "merge", 0, label="x")
        assert len(faults.fired_events()) == 1
    # counters and the fired log are restored on exit
    assert faults.next_index("merge") == base + 1
    assert len(faults.fired_events()) == 0


def test_nan_lace_deterministic():
    x = np.linspace(-1.0, 1.0, 64).astype(np.float32)
    a = np.asarray(faults.nan_lace(x, "salt"))
    b = np.asarray(faults.nan_lace(x, "salt"))
    c = np.asarray(faults.nan_lace(x, "other"))
    assert np.array_equal(a, b, equal_nan=True)
    assert np.isnan(a).sum() == max(1, x.size // 8)
    assert not np.array_equal(np.isnan(a), np.isnan(c))
    ints = np.arange(32, dtype=np.int32)
    assert faults.nan_lace(ints, "salt") is ints  # non-float: unchanged


def test_corrupt_breaks_sortedness():
    x = np.sort(np.random.default_rng(0).standard_normal(64).astype(np.float32))
    y = np.asarray(faults.corrupt(x))
    assert not np.all(np.diff(y) >= 0)
    assert np.array_equal(np.sort(y), x)  # a swap, not a rewrite
    const = np.zeros(8, np.float32)
    assert faults.corrupt(const) is const
    k, v = faults.corrupt((x, x.copy()))
    assert not np.all(np.diff(np.asarray(k)) >= 0)
    assert np.array_equal(np.asarray(v), x)  # values untouched


# ---------------------------------------------------------------------------
# guarded kernel ops: one bit-identity fuzz per fault class
# ---------------------------------------------------------------------------


def _ops_cases():
    rng = np.random.default_rng(7)
    a = np.sort(rng.standard_normal(192)).astype(np.float32)
    b = np.sort(rng.standard_normal(128)).astype(np.float32)
    av = rng.integers(0, 10_000, a.shape[0]).astype(np.int32)
    bv = rng.integers(0, 10_000, b.shape[0]).astype(np.int32)
    A = np.sort(rng.standard_normal((3, 96)).astype(np.float32), axis=1)
    B = np.sort(rng.standard_normal((3, 64)).astype(np.float32), axis=1)
    AV = rng.integers(0, 10_000, A.shape).astype(np.int32)
    BV = rng.integers(0, 10_000, B.shape).astype(np.int32)
    a_lens = rng.integers(0, A.shape[1] + 1, A.shape[0]).astype(np.int32)
    b_lens = rng.integers(0, B.shape[1] + 1, B.shape[0]).astype(np.int32)
    x = rng.standard_normal(256).astype(np.float32)
    X = rng.standard_normal((3, 128)).astype(np.float32)
    XV = rng.integers(0, 99, X.shape).astype(np.int32)
    x_lens = rng.integers(1, X.shape[1] + 1, X.shape[0]).astype(np.int32)
    runs = np.sort(rng.standard_normal((4, 64)).astype(np.float32), axis=1)
    j = jnp.asarray
    return [
        ("merge", lambda: ops.merge(j(a), j(b))),
        ("merge_kv", lambda: ops.merge_kv(j(a), j(av), j(b), j(bv))),
        ("merge_batched", lambda: ops.merge_batched(j(A), j(B))),
        ("merge_kv_batched",
         lambda: ops.merge_kv_batched(j(A), j(AV), j(B), j(BV))),
        ("merge_batched_ragged",
         lambda: ops.merge_batched_ragged(j(A), j(B), j(a_lens), j(b_lens))),
        ("merge_kv_batched_ragged",
         lambda: ops.merge_kv_batched_ragged(
             j(A), j(AV), j(B), j(BV), j(a_lens), j(b_lens))),
        ("sort", lambda: ops.sort(j(x))),
        ("sort_kv", lambda: ops.sort_kv(j(x), j(np.arange(x.size, dtype=np.int32)))),
        ("sort_batched", lambda: ops.sort_batched(j(X))),
        ("sort_kv_batched", lambda: ops.sort_kv_batched(j(X), j(XV))),
        ("merge_k", lambda: ops.merge_k(j(runs))),
        ("topk_batched", lambda: ops.topk_batched(j(X), 8)),
        ("topk_batched_ragged",
         lambda: ops.topk_batched_ragged(j(X), 8, j(x_lens))),
    ]


_OPS_CASES = _ops_cases()


@pytest.mark.parametrize("op,thunk", _OPS_CASES, ids=[c[0] for c in _OPS_CASES])
def test_launch_fault_degrades_bit_identical(op, thunk):
    """A wildcard launch fault burns every non-final attempt; the surviving
    oracle edge must reproduce the clean chain's output bit for bit."""
    clean = thunk()
    res.reset_health()
    with faults.inject(f"launch:{op}:*"):
        with pytest.warns(FallbackWarning, match="degraded"):
            degraded = thunk()
        rec = res.health(op)
        assert rec.fallbacks >= 1 and rec.launch_failures >= 1
        assert rec.served_by and "pallas" not in max(rec.served_by)
    _tree_equal(degraded, clean)


def test_vmem_fault_rejected_in_preflight():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal(256).astype(np.float32))
    clean = ops.sort(x)
    res.reset_health()
    with faults.inject("vmem:sort:*:pallas"):
        with pytest.warns(FallbackWarning, match="degraded"):
            degraded = ops.sort(x)
        rec = res.health("sort")
        assert rec.precondition_rejects >= 1
        assert rec.served_by.get("core") == 1
    _tree_equal(degraded, clean)


def test_exchange_fault_caught_by_verifier():
    rng = np.random.default_rng(12)
    a = jnp.asarray(np.sort(rng.standard_normal(192).astype(np.float32)))
    b = jnp.asarray(np.sort(rng.standard_normal(128).astype(np.float32)))
    clean = ops.merge(a, b)
    res.reset_health()
    with faults.inject("exchange:merge:*:pallas-hier"):
        with pytest.warns(FallbackWarning, match="verify failed"):
            degraded = ops.merge(a, b)
        rec = res.health("merge")
        assert rec.verify_failures == 1
        assert rec.served_by.get("pallas-matrix") == 1
    _tree_equal(degraded, clean)


def test_nan_fault_sort_total_order_oracle():
    """NaN-laced keys must come out in total-order (NaN-last, stable)."""
    rng = np.random.default_rng(13)
    x = rng.standard_normal(256).astype(np.float32)
    with faults.inject("nan:sort:*"):
        out = np.asarray(ops.sort(jnp.asarray(x)))
    laced = np.asarray(faults.nan_lace(x, "sort:0:0"))
    assert np.isnan(laced).any()
    _tree_equal(out, _tok_stable_sort(laced))


def test_nan_fault_merge_repaired_by_resort():
    """Lacing breaks the sorted-input precondition of every merge route;
    only the terminal re-sort edge can serve, and it must match the
    total-order oracle on the laced operands exactly."""
    rng = np.random.default_rng(14)
    a = np.sort(rng.standard_normal(192).astype(np.float32))
    b = np.sort(rng.standard_normal(128).astype(np.float32))
    res.reset_health()
    with faults.inject("nan:merge:*"):
        out = np.asarray(ops.merge(jnp.asarray(a), jnp.asarray(b)))
        assert res.health("merge").served_by.get("core-resort") == 1
    la = np.asarray(faults.nan_lace(a, "merge:0:0"))
    lb = np.asarray(faults.nan_lace(b, "merge:0:1"))
    _tree_equal(out, _tok_stable_sort(np.concatenate([la, lb])))


def test_launch_fault_ssm_scan_degrades_to_ref():
    rng = np.random.default_rng(15)
    bsz, s, d, st = 1, 32, 16, 4
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (bsz, s, d)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((bsz, s, d)).astype(np.float32))
    bmat = jnp.asarray(rng.standard_normal((bsz, s, st)).astype(np.float32))
    cmat = jnp.asarray(rng.standard_normal((bsz, s, st)).astype(np.float32))
    a = jnp.asarray(-np.abs(rng.standard_normal((d, st))).astype(np.float32))
    y_ref, h_ref = ssm_scan_ref(dt, x, bmat, cmat, a)
    res.reset_health()
    with faults.inject("launch:ssm_scan_pallas:*"):
        with pytest.warns(FallbackWarning, match="degraded"):
            y, h = ssm_scan_pallas(dt, x, bmat, cmat, a)
        assert res.health("ssm_scan_pallas").served_by.get("core-ref") == 1
    _tree_equal((y, h), (y_ref, h_ref))


def test_exhausted_chain_raises_with_log():
    with faults.inject("launch:merge:*:"):
        # no-match wildcard spares the oracle; force it too with a 2nd clause
        with faults.inject("launch:merge:*:core"):
            with pytest.raises(GuardedDispatchError) as exc:
                ops.merge(jnp.arange(8.0), jnp.arange(8.0))
            assert "core-resort" in str(exc.value)
    assert res.health("merge").exhausted == 1


def test_guard_disabled_env_bypasses(monkeypatch):
    monkeypatch.setenv("REPRO_GUARD", "0")
    assert not res.guard_enabled()
    rng = np.random.default_rng(16)
    x = jnp.asarray(rng.standard_normal(128).astype(np.float32))
    with faults.inject("launch:sort:*"):
        out = ops.sort(x)  # guard off: primary path runs, no fault hooks
    assert res.health("sort").calls == 0
    _tree_equal(out, jnp.sort(x))
    monkeypatch.delenv("REPRO_GUARD")
    assert res.guard_enabled()


# ---------------------------------------------------------------------------
# env-driven plan (`make test-faults` re-runs only this under REPRO_FAULTS)
# ---------------------------------------------------------------------------


def test_env_plan_end_to_end():
    """Under the Makefile's REPRO_FAULTS plan the first call of each named
    op degrades and still matches its oracle; later calls run clean."""
    plan = os.environ.get("REPRO_FAULTS", "")
    if not plan:
        pytest.skip("REPRO_FAULTS not set (run via `make test-faults`)")
    assert faults.active()
    rng = np.random.default_rng(17)
    a = np.sort(rng.standard_normal(192).astype(np.float32))
    b = np.sort(rng.standard_normal(128).astype(np.float32))
    x = rng.standard_normal(256).astype(np.float32)

    # call index 0: the env plan fires (launch:merge:0 / launch:sort:0)
    faults.reset_counters()
    m0 = np.asarray(ops.merge(jnp.asarray(a), jnp.asarray(b)))
    s0 = np.asarray(ops.sort(jnp.asarray(x)))
    assert {e.op for e in faults.fired_events()} >= {"merge", "sort"}
    # call index 1: clean
    m1 = np.asarray(ops.merge(jnp.asarray(a), jnp.asarray(b)))
    s1 = np.asarray(ops.sort(jnp.asarray(x)))
    oracle_m = np.sort(np.concatenate([a, b]), kind="stable")
    for got in (m0, m1):
        _tree_equal(got, oracle_m)
    for got in (s0, s1):
        _tree_equal(got, np.sort(x, kind="stable"))
    assert res.health("merge").fallbacks >= 1
    assert res.health("sort").fallbacks >= 1


# ---------------------------------------------------------------------------
# serving engine: graceful degradation
# ---------------------------------------------------------------------------


def _make_engine(**kw):
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_params(cfg, jax.random.key(0))
    return cfg, ServingEngine(cfg, params, batch=2, max_seq=32, **kw)


def _requests(cfg, n, rng, **kw):
    from repro.serving.engine import Request

    return [
        Request(
            uid=i,
            prompt=rng.integers(1, cfg.vocab_size, 4).astype(np.int32),
            max_new_tokens=2,
            temperature=0.0,
            **kw,
        )
        for i in range(n)
    ]


def test_serving_shed_and_deadline_without_decode():
    """Queue shedding and deadline expiry never touch the decode path, so
    they work even while the backend is down (permanent injected fault)."""
    cfg, eng = _make_engine(max_pending=1)
    rng = np.random.default_rng(0)
    r1, r2 = _requests(cfg, 2, rng, deadline_ticks=2)
    with pytest.warns(FallbackWarning, match="shed"):
        eng.submit(r1)
        eng.submit(r2)  # queue full -> shed at submit time
    assert r2.status == "shed" and "queue full" in r2.reason
    with faults.inject("launch:serving.decode:*"):
        rep = eng.run_until_done(max_ticks=10)
    assert rep.statuses[r1.uid] == "timed_out"
    assert "deadline_ticks=2" in rep.reasons[r1.uid]
    assert rep.shed == 1 and rep.timed_out == 1 and rep.completed == 0
    assert not rep.ok()
    assert len(eng.done) == 2  # nothing dropped silently


def test_serving_transient_fault_retries_and_completes():
    """A transient decode fault costs retries + backoff ticks but every
    request still completes — zero drops, partials never surface."""
    cfg, eng = _make_engine(max_retries=3, backoff_base=1, backoff_cap=4)
    rng = np.random.default_rng(1)
    reqs = _requests(cfg, 3, rng)
    for r in reqs:
        eng.submit(r)
    with faults.inject("launch:serving.decode:1"):
        rep = eng.run_until_done(max_ticks=200)
    assert rep.completed == len(reqs) and rep.ok()
    assert rep.retries == 1
    assert sorted(rep.statuses) == [r.uid for r in reqs]
    for r in reqs:
        assert r.status == "completed"
        assert len(r.generated) == r.max_new_tokens


def test_serving_permanent_fault_never_wedges():
    """A permanently failing backend sheds the queue with reasons instead
    of hanging; the engine survives and the report accounts for all."""
    cfg, eng = _make_engine(max_retries=2)
    rng = np.random.default_rng(2)
    reqs = _requests(cfg, 2, rng)
    for r in reqs:
        eng.submit(r)
    with faults.inject("launch:serving.decode:*"):
        rep = eng.run_until_done(max_ticks=30)
    assert rep.ticks == 30 and not rep.ok()
    assert rep.completed == 0
    assert rep.shed + rep.timed_out + rep.failed == len(reqs)
    assert len(eng.done) == len(reqs)
    for r in reqs:
        assert rep.reasons[r.uid]  # every terminal status carries a reason
    # the engine recovered its retry state: a clean tick is a no-op, not a throw
    eng.step()


# ---------------------------------------------------------------------------
# distributed chains (subprocess, 8 fake CPU devices)
# ---------------------------------------------------------------------------


def run_with_devices(code: str, n: int = 8, timeout: int = 600) -> None:
    # mirrors tests/test_distributed.py: fake device count in a subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("REPRO_FAULTS", None)  # the inline scripts inject their own plans
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"


def test_distributed_faults_multi_device():
    run_with_devices("""
        import warnings
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed as D
        from repro.runtime import faults
        from repro.runtime import resilience as res
        warnings.simplefilter("ignore")
        rng = np.random.default_rng(0)

        # merge: window exchange down -> gather serves, bit-identical
        a = np.sort(rng.standard_normal(512)).astype(np.float32)
        b = np.sort(rng.standard_normal(256)).astype(np.float32)
        clean = np.asarray(D.distributed_merge(jnp.array(a), jnp.array(b)))
        assert np.array_equal(clean, np.sort(np.concatenate([a, b])))
        res.reset_health()
        with faults.inject("launch:distributed_merge:*:window"):
            out = np.asarray(D.distributed_merge(jnp.array(a), jnp.array(b)))
        assert np.array_equal(out, clean)
        assert res.health("distributed_merge").served_by.get("gather") == 1

        # merge: corrupted window exchange is caught by the always-on verify
        res.reset_health()
        with faults.inject("exchange:distributed_merge:*:window"):
            out = np.asarray(D.distributed_merge(jnp.array(a), jnp.array(b)))
        assert np.array_equal(out, clean)
        assert res.health("distributed_merge").verify_failures == 1

        # sort: sampled splitters down -> capacity escalation (shape grows;
        # slice by the returned counts), still the exact global sort
        x = rng.standard_normal(2048).astype(np.float32)
        res.reset_health()
        with faults.inject("launch:distributed_sort:*:sample"):
            s, cnt, ovf = D.distributed_sort(jnp.array(x))
        assert res.health("distributed_sort").served_by.get("capacity-2x") == 1
        s, cnt = np.asarray(s), np.asarray(cnt)
        cap = s.shape[0] // cnt.size
        got = np.concatenate([s[i*cap:i*cap+cnt[i]] for i in range(cnt.size)])
        assert np.array_equal(got, np.sort(x))

        # sort: every exchange route down -> single-host total-order re-sort
        res.reset_health()
        with faults.inject("launch:distributed_sort:*"):
            s, cnt, ovf = D.distributed_sort(jnp.array(x))
        assert res.health("distributed_sort").served_by.get("core-resort") == 1
        assert np.array_equal(np.asarray(s)[:int(np.asarray(cnt).sum())], np.sort(x))

        # topk: butterfly down -> gather, then everything down -> core
        clean_v, clean_i = D.distributed_topk(jnp.array(x), 16)
        res.reset_health()
        with faults.inject("launch:distributed_topk:*:butterfly"):
            v, i = D.distributed_topk(jnp.array(x), 16)
        assert np.array_equal(np.asarray(v), np.asarray(clean_v))
        assert np.array_equal(np.asarray(i), np.asarray(clean_i))
        assert res.health("distributed_topk").served_by.get("gather") == 1
        res.reset_health()
        with faults.inject("launch:distributed_topk:*"):
            v, i = D.distributed_topk(jnp.array(x), 16)
        assert np.array_equal(np.asarray(v), np.asarray(clean_v))
        assert np.array_equal(np.asarray(i), np.asarray(clean_i))
        assert res.health("distributed_topk").served_by.get("core-topk") == 1
        print("ok")
    """)
