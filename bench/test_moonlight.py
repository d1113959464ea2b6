"""The Moonlight-16B-A3B cell on the CPU: the program against the plain
reference on seeded weights, the mapping of the configuration file, the
traffic's request sizes, the work function and the roofline reader by
hand, and the ``serving-by-config`` kind run at a small size.

Tolerances: the program and the reference both compute in float32 here
(the reference at ``HIGHEST``; on the CPU every float32 matmul is exact
float32), so their logits differ by summation order alone, ~1e-6 of the
largest logit over a few thousand terms; 2e-5 leaves room for that, and
the program served in bfloat16 misses it by orders of magnitude (checked
below)."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import registry, result, seeds
from harness.record import Record
from harness.trace import Trace, Window

BENCH = registry.benchmark()
CELL = "moonlight-7l.decode"
CONFIG = "moonlight-16b-a3b-7l"
TOL = 2e-5  # float32 summation order, relative to the largest logit (module docstring)
# published widths cut for a CPU: 64 experts top-6, the whole vocabulary, 3 layers (1 dense, 2 MoE)
SMALL = {"hidden_size": 128, "intermediate_size": 192, "moe_intermediate_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "num_hidden_layers": 3}


class Chip:
    """Stands in for the device the harness would have found."""

    platform, device_kind = "cpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


def _cfg():
    return dict(registry.config(CONFIG), **SMALL)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# -- the program against the reference ----------------------------------------


def _program_logits(cfg, w, toks, n_prompt, dtype):
    """Prefill logits of the prompt's last token, then one decode call per
    further token through the latent cache: the logits of every position
    from ``n_prompt - 1`` on."""
    import dataclasses

    from repro.models import forward_decode, forward_prefill

    ref = registry.reference(CONFIG)
    pc = dataclasses.replace(ref.program_config(cfg), dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype), w)
    t = len(toks)
    last, caches, _ = jax.jit(lambda p, x: forward_prefill(pc, p, {"tokens": x}, cache_len=t))(
        params, jnp.asarray(toks[None, :n_prompt]))
    step = jax.jit(lambda p, c, x, pos: forward_decode(pc, p, c, x, pos))
    out = [last[0]]
    for i in range(n_prompt, t):
        logits, caches = step(params, caches, jnp.asarray(toks[None, i : i + 1]), jnp.array([i], jnp.int32))
        out.append(logits[0])
    return jnp.stack(out)


@pytest.fixture(scope="module")
def seeded():
    cfg = _cfg()
    ref = registry.reference(CONFIG)
    w = ref.weights(cfg, seeds.jax_key(2**33 + 11, 1))
    toks = np.asarray(jax.random.randint(jax.random.key(3), (14,), 1, cfg["vocab_size"]), np.int32)
    want = ref.logits(cfg, jax.tree.map(lambda a: a.astype(jnp.float32), w), toks)
    return cfg, w, toks, want


def test_prefill_then_decode_matches_the_reference(seeded):
    cfg, w, toks, want = seeded
    got = _program_logits(cfg, w, toks, 6, "float32")
    assert _rel(got, want[5:]) < TOL


def test_bfloat16_program_misses_the_tolerance(seeded):
    """The tolerance separates the stated float32 from bfloat16."""
    cfg, w, toks, want = seeded
    got = _program_logits(cfg, w, toks, 6, "bfloat16")
    assert _rel(got, want[5:]) > 100 * TOL


def test_reference_bfloat16_pass_and_router_flips(seeded):
    """The bfloat16 witness rounds (it misses the float32 tolerance) and
    stays far closer to float32 than the fp8 control; the flips are one
    flag a position and MoE layer."""
    cfg, w, toks, want = seeded
    ref = registry.reference(CONFIG)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    bf = _rel(ref.logits(cfg, w32, toks, "bfloat16"), want)
    assert 100 * TOL < bf < _rel(ref.logits(cfg, w32, toks, "fp8"), want) / 2
    flips = ref.router_flips(cfg, w32, toks)
    assert flips.shape == (len(toks), 2) and flips.dtype == jnp.bool_
    with pytest.raises(ValueError):
        ref.logits(cfg, w32, toks, "float16")


def test_reference_rotates_interleaved_pairs():
    """Rotary pairs (x[2i], x[2i+1]) in the reference; the program's
    reorder to halves computes the same scores: a reference that rotated
    halves instead would disagree with the program."""
    ref = registry.reference(CONFIG)
    x = jnp.arange(8.0)[None, :]
    y = ref._rope(x, jnp.array([1]), 10_000.0)
    c, s = np.cos(1.0), np.sin(1.0)  # the first pair turns by pos * theta^0
    np.testing.assert_allclose(np.asarray(y)[0, :2], [0 * c - 1 * s, 1 * c + 0 * s], rtol=1e-6)


# -- the configuration file ------------------------------------------------------


def test_program_config_maps_the_file():
    cfg = registry.config(CONFIG)
    pc = registry.reference(CONFIG).program_config(cfg)
    assert (pc.num_layers, pc.first_k_dense, pc.stack_layers) == (7, 1, 6)
    assert (pc.d_model, pc.dense_ff, pc.d_ff, pc.shared_expert_ff) == (2048, 11264, 1408, 2816)
    assert (pc.kv_lora_rank, pc.qk_nope_head_dim, pc.qk_rope_head_dim, pc.v_head_dim) == (512, 128, 64, 128)
    assert (pc.num_experts, pc.experts_per_token, pc.router_scoring, pc.routed_scaling) == (64, 6, "sigmoid", 2.446)
    assert (pc.vocab_size, pc.rms_eps, pc.rope_theta, pc.tie_embeddings, pc.dtype) == \
        (163840, 1e-5, 50000.0, False, "bfloat16")
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 27}


@pytest.mark.parametrize("key,value", [("scoring_func", "softmax"), ("q_lora_rank", 1536),
                                       ("topk_method", "greedy"), ("norm_topk_prob", False)])
def test_program_config_refuses_another_block(key, value):
    cfg = dict(registry.config(CONFIG), **{key: value})
    with pytest.raises(ValueError):
        registry.reference(CONFIG).program_config(cfg)


def test_config_holds_the_catalog_numbers():
    cfg = registry.config(CONFIG)
    published = {"hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512, "moe_intermediate_size": 1408,
                 "n_routed_experts": 64, "n_shared_experts": 2, "num_attention_heads": 16, "num_experts_per_tok": 6,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "vocab_size": 163840,
                 "rope_theta": 50000, "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-05,
                 "first_k_dense_replace": 1, "max_position_embeddings": 8192}
    assert {k: cfg[k] for k in published} == published


# -- traffic ---------------------------------------------------------------------


def test_every_seed_gets_the_same_request_sizes():
    plan = registry.kind("serving").plan
    tr = registry.traffic("decode-greedy16")
    n = tr["pool"]

    def stream(seed):
        return [r for _, r in zip(range(n), plan(tr, seed, 163840))]

    a, b = stream(11), stream(2**33 + 11)
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])  # the seed draws the tokens
    for i in range(0, n, 8):  # block by block, the same (prompt, output) sizes
        assert sorted((len(r["prompt"]), r["max_new"]) for r in a[i:i + 8]) == \
            sorted((len(r["prompt"]), r["max_new"]) for r in b[i:i + 8])
    for r in a:
        assert 32 <= len(r["prompt"]) <= 128 and 256 <= r["max_new"] <= 1024
        assert len(r["prompt"]) + r["max_new"] < tr["max_seq"]
    assert tr["greedy_clients"] == tr["clients"] == tr["slots"] == 16


# -- work function and reader by hand --------------------------------------------


def test_work_by_hand():
    w = registry.work(CONFIG)
    cfg = registry.config(CONFIG)
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048  # 13,762,560
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + 6 * expert + 2 * expert  # router, 6 routed, the shared pair
    active = 7 * mla + 3 * 2048 * 11264 + 6 * moe + 163840 * 2048
    assert w.active_params(cfg) == active == 917_110_784
    assert w.flops_per_token(cfg, 0) == 2 * active
    # attention over 1,000 positions: 7 layers x 16 heads x (2 x 192 + 2 x 128)
    assert w.flops_per_token(cfg, 1000) - w.flops_per_token(cfg, 0) == 7 * 16 * 640 * 1000
    fixed = 7 * mla + 3 * 2048 * 11264 + 6 * (2048 * 64 + 64 + 2 * expert) + 7 * (2 * 2048 + 512) + 2048 + 2048 * 163840
    assert w.fixed_weights(cfg) == fixed
    # 2 calls that reached 300 experts and attended 10,000 latent rows, in bfloat16
    assert w.least_bytes(cfg, 2, 300, 10_000) == 2 * (2 * fixed + 300 * expert + 10_000 * 576)


def _record(steps, ops, spans, window):
    return Record(kind="serving", cell={"config": registry.config(CONFIG)}, peaks={"hbm_bytes_per_s": 819e9},
                  work=registry.work(CONFIG), setup_s=1.0, window=window, traced_window=window, steps=steps,
                  trace=Trace(ops, spans, window))


def test_decode_roofline_reader_by_hand():
    counts = {"serving.decode_calls": 1, "moe.experts_reached": 300, "mla.latent_positions": 7000}
    steps = [{"start": 0.0, "end": 1.0, "tokens": 16, "positions": [], "counts": counts},
             {"start": 1.0, "end": 2.0, "tokens": 16, "positions": [], "counts": dict(counts, **{"serving.decode_calls": 3})},
             {"start": 2.0, "end": 3.5, "tokens": 16, "positions": [], "counts": counts}]  # ends past the window
    ops = [(0, "jit_decode_step/fusion.1", 0.1, 0.3), (0, "jit_decode_step/while.2", 0.2, 0.4),
           (0, "jit_argmax/reduce.3", 0.5, 0.9), (0, "jit_decode_step/fusion.1", 1.2, 1.5)]
    spans = [("bench.step", 0.0, 1.0), ("bench.step", 1.0, 2.0), ("bench.step", 2.0, 3.5)]
    rec = _record(steps, ops, spans, (0.0, 3.0))
    work, cfg = rec.work, rec.cell["config"]
    least = work.least_bytes(cfg, 4, 600, 14_000)
    want = 100 * least / 819e9 / (0.3 + 0.3)  # decode ops only, overlaps counted once
    assert registry.reader("decode_roofline.mla").read(rec) == pytest.approx(want)
    # no counts (a program without them), or no trace: nothing to read
    bare = [{k: v for k, v in s.items() if k != "counts"} for s in steps]
    assert registry.reader("decode_roofline.mla").read(_record(bare, ops, spans, (0.0, 3.0))) is None
    rec.trace = None
    assert registry.reader("decode_roofline.mla").read(rec) is None


# -- the kind on the CPU ---------------------------------------------------------


class StepWindow(Window):
    """A window of a fixed number of engine steps."""

    STEPS = 24

    def over(self, now):
        self.steps = getattr(self, "steps", 0) + 1
        if self.steps < self.STEPS:
            return False
        self.end, self.compiled = now, (0, 0)
        return True


def small(**sizes):
    cell = copy.deepcopy(registry.cell(CELL, BENCH))
    cell["config"].update(SMALL, **sizes)
    tr = cell["traffic"]
    tr.update(max_seq=64, clients=4, slots=4, greedy_clients=4)
    tr["prompt"].update(min=4, max=16, median=8)
    tr["output"].update(min=4, max=24, median=12)
    tr["check"]["tokens_per_kind"] = 48
    return cell


def run(cell):
    kind = registry.kind("serving-by-config")
    kind.Window = StepWindow
    rec = kind.run(cell, seed=2**33 + 3, seconds=0.0, traced=False, devices=[Chip()], t0=0.0, log=lambda m: None)
    return rec, result.line(cell, rec, [Chip()], False)


def test_sound_run_is_correct_and_counts_its_calls():
    rec, out = run(small())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    calls = [s["counts"]["serving.decode_calls"] for s in rec.steps]
    assert calls[0] > 4 and all(c >= 1 for c in calls)  # the first step fills 4 prompts, a call a token
    for s in rec.steps[1:]:
        if not s["first_token"]:  # one lockstep call of 4 busy slots: 3 layers attend each slot's positions
            assert s["counts"]["serving.decode_calls"] == 1 and s["tokens"] == 4
            assert 6 <= s["counts"]["moe.experts_reached"] <= 2 * 4 * 6  # 2 MoE layers, 4 tokens, top-6
            assert s["counts"]["mla.latent_positions"] == 3 * sum(p + 1 for p in s["positions"])


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._sample

    def broken(self, req, row):
        return (real(self, req, row) + 1) % self.cfg.vocab_size  # every token altered where produced

    monkeypatch.setattr(ServingEngine, "_sample", broken)
    _, out = run(small())
    assert not out["correct"]
    assert out["checks"]["greedy_gap_p90"]["value"] > out["checks"]["greedy_gap_p90"]["limit"]


def test_control_is_not_correct():
    """At the cell's 7 layers the fp8 control's greedy gaps pass the cell's
    own limit and the program's do not (on this CPU: 0.25 and 0.76 against
    0.45, 59 tokens)."""
    cell = small(num_hidden_layers=7)
    kind = registry.kind("serving-by-config")
    kind.Window = StepWindow
    res = kind.control(cell, 7, 0.0, [Chip()], lambda m: None)
    limit = cell["traffic"]["check"]["greedy_gap_p90"]
    assert res["program"]["greedy_gap"]["p90"] <= limit < res["control"]["greedy_gap"]["p90"]
    # the witnesses: the bfloat16 pass reads its own gaps, the flips count the program's tokens
    assert res["bfloat16_reference"]["greedy_gap"]["n"] == res["program"]["greedy_gap"]["n"]
    flips = res["router_flips"]
    assert set(flips) == {"0.1", str(limit)}
    assert flips["0.1"]["wide"] == res["program"]["greedy_gap"]["above_0.1"]
    for f in flips.values():
        assert f["tokens"] == res["program"]["greedy_gap"]["n"]
        assert 0 <= f["wide_flipped"] <= min(f["wide"], f["flipped"])
