"""``correct`` comes out false when the timed path is broken, and the
control does not pass: each cell's kind driven on the CPU at a size a test
run can hold, past the harness's look for a chip."""

import copy

import pytest

from harness import registry, result
from harness.trace import Window

BENCH = registry.benchmark()


class Chip:
    """Stands in for the device the harness would have found."""

    platform, device_kind = "cpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


SMALL_TABLES = {"orders_rows": 200, "lineitem_rows": 800}
# the published vocabulary, and layers enough, that fp8 rounding reorders the top candidates
SMALL_MODEL = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4, "num_hidden_layers": 4,
               "num_key_value_heads": 2, "head_dim": 32, "num_local_experts": 4}


def small(name):
    cell = copy.deepcopy(registry.cell(name, BENCH))
    if cell["traffic"]["kind"] == "library":
        cell["config"].update(SMALL_TABLES)
    else:
        cell["config"].update(SMALL_MODEL)
        tr = cell["traffic"]
        tr.update(max_seq=64)
        tr["prompt"].update(min=4, max=16, median=8)
        tr["output"].update(min=4, max=24, median=12)
        tr["check"]["tokens_per_kind"] = 48
    return cell


def run(cell, seconds=0.5):
    kind = registry.kind(cell["traffic"]["kind"])
    rec = kind.run(cell, seed=2**33 + 3, seconds=seconds, traced=False, devices=[Chip()], t0=0.0,
                   log=lambda msg: None)
    return result.line(cell, rec, [Chip()], False)


LIBRARY = [w["name"] for w in BENCH["workloads"] if registry.traffic(w["traffic"])["kind"] == "library"]
SERVING = [w["name"] for w in BENCH["workloads"] if registry.traffic(w["traffic"])["kind"] == "serving"]


@pytest.mark.parametrize("name", LIBRARY)
def test_library_control_is_not_correct(name):
    cell = small(name)
    res = registry.kind("library").control(cell, 5, 0.0, [Chip()], lambda m: None)
    limit = cell["traffic"]["check"]["mismatched_rows"]
    assert res["program"]["mismatched_rows"] <= limit < res["control"]["mismatched_rows"]


@pytest.mark.parametrize("name", LIBRARY)
def test_library_sound_run_is_correct(name):
    out = run(small(name))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", LIBRARY)
def test_library_guard_fallback_is_not_correct(name):
    from repro.runtime import faults

    op = registry.traffic(registry.cell(name, BENCH)["traffic_name"])["op"]
    with faults.inject(f"launch:{op}:*:pallas-hier"):  # the kernel fails, the guard serves another edge
        out = run(small(name))
    assert not out["correct"] and out["checks"]["failed_calls"]["value"] == out["failed"] > 0
    assert out["checks"]["mismatched_rows"]["value"] == 0


@pytest.mark.parametrize("name", LIBRARY)
def test_library_altered_answer_is_not_correct(name, monkeypatch):
    from repro.kernels import ops

    op = registry.traffic(registry.cell(name, BENCH)["traffic_name"])["op"]
    real = getattr(ops, op)

    def broken(*args, **kw):
        keys, values = real(*args, **kw)
        return keys, values.at[values.shape[0] // 2].add(1)  # one row id altered where produced

    monkeypatch.setattr(ops, op, broken)
    out = run(small(name))
    assert not out["correct"] and out["checks"]["mismatched_rows"]["value"] > 0


class StepWindow(Window):
    """A window of a fixed number of engine steps, so that the sample the
    control is read on does not depend on how fast this CPU runs."""

    STEPS = 24

    def over(self, now):
        self.steps = getattr(self, "steps", 0) + 1
        if self.steps < self.STEPS:
            return False
        self.end, self.compiled = now, (0, 0)
        return True


@pytest.fixture(scope="module")
def serving_control():
    cell = small(SERVING[0])
    kind = registry.kind("serving")
    kind.Window = StepWindow
    return cell, kind.control(cell, 7, 0.0, [Chip()], lambda m: None)


def test_serving_control_is_not_correct(serving_control):
    cell, res = serving_control
    limit = cell["traffic"]["check"]["greedy_gap_p90"]
    assert res["program"]["greedy_gap"]["p90"] <= limit < res["control"]["greedy_gap"]["p90"]


@pytest.mark.parametrize("name", SERVING)
def test_serving_altered_token_is_not_correct(name, monkeypatch):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._sample

    def broken(self, req, row):
        return (real(self, req, row) + 1) % self.cfg.vocab_size  # every token altered where produced

    monkeypatch.setattr(ServingEngine, "_sample", broken)
    out = run(small(name), seconds=1.0)
    assert not out["correct"]
    assert out["checks"]["greedy_gap_p90"]["value"] > out["checks"]["greedy_gap_p90"]["limit"]
