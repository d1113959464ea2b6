"""Batched serving engine: slot-based continuous batching.

A fixed pool of ``batch`` slots; finished/empty slots are refilled from
the request queue (prefill), all occupied slots decode in lockstep (one
jitted decode step per tick).  Per-slot absolute positions make the
lockstep correct for ragged prompt lengths.  Sampling is one jitted
device call a tick (``sample_step``, :func:`repro.serving.sampler.sample_batch`)
over the logits the decode call left on the device: greedy rows take the
argmax, top-k rows draw from ``jax.lax.top_k``'s candidates (the
merge-path top-k's tie order), and the host reads back one token a slot.

Graceful degradation
--------------------
The engine never drops a request silently: every submitted request ends
in ``engine.done`` with an explicit terminal ``status`` —

* ``completed`` — generated ``max_new_tokens`` (or hit the sequence cap);
* ``timed_out`` — exceeded its per-request ``deadline_ticks`` budget (or
  the engine ran out of ``run_until_done`` ticks) with its partial
  ``generated`` tokens preserved;
* ``shed``      — rejected at ``submit`` because the queue was full
  (``max_pending``), or never scheduled before the tick budget drained;
* ``failed``    — the decode step failed ``max_retries`` consecutive
  times while the request was in flight (partial tokens preserved).

A failed tick (an exception out of the jitted decode — e.g. an injected
``launch:serving.decode`` fault from :mod:`repro.runtime.faults`) does
not kill the engine: it backs off for ``min(backoff_base * 2**(streak-1),
backoff_cap)`` ticks and retries; only after ``max_retries`` consecutive
failures are the in-flight requests terminated (``failed``), after which
the engine recovers and keeps serving the queue.  All timing is counted
in deterministic engine *ticks* — never wall clock — so every degradation
path replays exactly under the fault injector.

``run_until_done`` returns a :class:`ServingReport` summarising the
outcome; ``report.ok()`` is the zero-degradation check CI asserts on a
clean tree.

Counters
--------
Every call of the jitted ``decode_step`` (its device ops are named
``jit_decode_step/...`` in a profile) adds 1 to ``serving.decode_calls``
on the host.  The model's own counts, ``moe.experts_reached`` and
``mla.latent_positions`` (``forward_decode(..., stats=True)``), count the
slots that carry a real token (the prompt's slot in a prompt-token call,
the occupied slots in a lockstep call), are summed
on the device over a tick's calls and read with the tick's lockstep
tokens, so a prompt-token call waits on nothing.

Every call of the jitted ``sample_step`` (``jit_sample_step/...``) adds 1
to ``serving.sample_calls``: one a lockstep tick over every occupied
slot, and one after each refill for that slot's first token.  A call in
which some live slot samples at a temperature above 0, and so runs the
top-k branch, also adds 1 to ``serving.topk_calls``; an all-greedy call
runs the argmax alone.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import forward_decode, forward_prefill, init_caches
from repro.runtime import faults as _faults
from repro.runtime.resilience import FallbackWarning
from repro.telemetry import WALL, TickClock, get_telemetry
from repro.train.steps import _cast
from . import sampler as sampler_mod


def sample_step(logits, key, temperature, row_k, k):
    """Every slot's next token in one device call: ``(tokens (B,), next_key)``.
    Jitted under this name, so that its device ops read ``jit_sample_step/...``."""
    return sampler_mod.sample_batch(logits, key, temperature, k, row_k)


_sample_step = jax.jit(sample_step, static_argnames=("k",))

# the model's counts a decode call returns, in the order the engine sums them
DEVICE_COUNTERS = ("moe.experts_reached", "mla.latent_positions")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (L,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 => greedy
    topk: int = 40
    deadline_ticks: Optional[int] = None  # tick budget from submission; None = no deadline
    # outputs
    generated: Optional[List[int]] = None
    status: str = "pending"  # pending | completed | timed_out | shed | failed
    reason: str = ""


@dataclasses.dataclass
class ServingReport:
    """Outcome summary returned by :meth:`ServingEngine.run_until_done`."""

    ticks: int = 0
    completed: int = 0
    timed_out: int = 0
    shed: int = 0
    failed: int = 0
    retries: int = 0
    statuses: Dict[int, str] = dataclasses.field(default_factory=dict)
    reasons: Dict[int, str] = dataclasses.field(default_factory=dict)
    # serving metric block (tick histograms + occupancy/queue gauges),
    # folded in by run_until_done from the active telemetry registry
    telemetry: Dict[str, object] = dataclasses.field(default_factory=dict)

    def ok(self) -> bool:
        """True when every request completed and no tick was retried."""
        return self.timed_out == 0 and self.shed == 0 and self.failed == 0


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        batch: int,
        max_seq: int,
        seed: int = 0,
        max_pending: Optional[int] = None,
        max_retries: int = 3,
        backoff_base: int = 1,
        backoff_cap: int = 8,
    ):
        self.cfg = cfg
        self.compute_dtype = jnp.dtype(cfg.dtype)
        self.params = _cast(params, self.compute_dtype)
        self.batch = batch
        self.max_seq = max_seq
        self.key = jax.random.key(seed)
        self.caches = init_caches(cfg, batch, max_seq)
        self.pos = np.zeros(batch, np.int32)
        self.active: List[Optional[Request]] = [None] * batch
        self.pending: List[Request] = []
        self.done: Dict[int, Request] = {}
        self.max_pending = max_pending
        self.max_retries = max_retries
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.ticks = 0
        self.retries = 0
        self._cooldown = 0
        self._fail_streak = 0
        # Deterministic span clock: while the engine steps, telemetry
        # timestamps count engine ticks (never wall time), so a
        # fault-injected run replays to a byte-identical trace.
        self.tick_clock = TickClock()
        self._counts = jnp.zeros((len(DEVICE_COUNTERS),), jnp.int32)

        def decode_step(params, caches, counts, tok, pos, live):
            logits, caches, stats = forward_decode(cfg, params, caches, tok, pos, stats=True, live=live)
            return logits, caches, counts + jnp.stack([stats[k] for k in DEVICE_COUNTERS])

        self._decode_step = jax.jit(decode_step)

    def _decode(self, token, pos, live: np.ndarray) -> jax.Array:
        """One decode call: updates the caches and the device counts of the
        ``live`` slots, returns the logits."""
        get_telemetry().counter("serving.decode_calls").add(1)
        logits, self.caches, self._counts = self._decode_step(
            self.params, self.caches, self._counts, token, pos, jnp.asarray(live)
        )
        return logits

    def _sample_slots(self, logits, live: np.ndarray) -> jax.Array:
        """One ``sample_step`` over the ``live`` slots' rows of ``logits``,
        which stay on the device; returns the ``(B,)`` tokens, still there."""
        temperature = np.zeros(self.batch, np.float32)
        row_k = np.ones(self.batch, np.int32)
        for s in np.flatnonzero(live):
            temperature[s] = self.active[s].temperature
            row_k[s] = self.active[s].topk
        row_k = np.clip(row_k, 1, logits.shape[-1])
        drawn = live & (temperature > 0)
        # k is static: the widest live top-k row's, and with none the widest
        # live row's, so that an all-greedy call reuses a mixed call's program
        k = int(row_k[drawn if drawn.any() else live].max())
        tel = get_telemetry()
        tel.counter("serving.sample_calls").add(1)
        if drawn.any():
            tel.counter("serving.topk_calls").add(1)
        tokens, self.key = _sample_step(logits, self.key, jnp.asarray(temperature), jnp.asarray(row_k), k=k)
        return tokens

    def _read_tokens(self, tokens) -> np.ndarray:
        """The tokens on the host, with the device counts summed since the
        last read: one blocking read."""
        tokens_np, counts = jax.device_get((tokens, self._counts))
        self._counts = jnp.zeros_like(self._counts)
        tel = get_telemetry()
        for name, n in zip(DEVICE_COUNTERS, counts):
            tel.counter(name).add(int(n))
        return tokens_np

    # -- request lifecycle ------------------------------------------------

    def submit(self, req: Request) -> None:
        """Queue a request — or shed it, loudly, when the queue is full."""
        req.generated = []
        req._submit_tick = self.ticks
        if self.max_pending is not None and len(self.pending) >= self.max_pending:
            self._finish(req, "shed", f"queue full (max_pending={self.max_pending})")
            return
        req.status = "pending"
        self.pending.append(req)

    def _finish(self, req: Request, status: str, reason: str = "") -> None:
        req.status = status
        req.reason = reason
        if req.generated is None:
            req.generated = []
        self.done[req.uid] = req
        if status != "completed":
            warnings.warn(
                f"serving: request {req.uid} {status}"
                + (f" ({reason})" if reason else ""),
                FallbackWarning,
                stacklevel=4,
            )

    def _expire_deadlines(self) -> None:
        """Terminate (loudly) every request past its tick budget."""
        for slot in range(self.batch):
            req = self.active[slot]
            if req is not None and self._past_deadline(req):
                self._finish(req, "timed_out", f"deadline_ticks={req.deadline_ticks} exceeded")
                self.active[slot] = None
        kept = []
        for req in self.pending:
            if self._past_deadline(req):
                self._finish(req, "timed_out", f"deadline_ticks={req.deadline_ticks} in queue")
            else:
                kept.append(req)
        self.pending = kept

    def _past_deadline(self, req: Request) -> bool:
        if req.deadline_ticks is None:
            return False
        return self.ticks - getattr(req, "_submit_tick", 0) >= req.deadline_ticks

    # -- decode -----------------------------------------------------------

    def _fill_slot(self, slot: int, req: Request) -> np.int32:
        """Prefill one request into a slot by stepping its prompt tokens,
        then sample its first token; returns that token, read back.

        Slot-wise decode-based prefill keeps the engine simple (batched
        prompt prefill is the launch/dryrun `prefill` path).  The first
        token comes from the same ``sample_step`` as a lockstep tick's,
        with only this slot live, on the last prompt call's logits.
        """
        prompt = req.prompt.astype(np.int32)
        only = np.arange(self.batch) == slot
        for t, tok in enumerate(prompt):
            token = jnp.zeros((self.batch, 1), jnp.int32).at[slot, 0].set(int(tok))
            pos = jnp.asarray(np.where(only, t, self.pos), jnp.int32)
            logits = self._decode(token, pos, only)
        self.pos[slot] = len(prompt)
        self.active[slot] = req
        return jax.device_get(self._sample_slots(logits, only))[slot]

    def _sample(self, req: Request, row) -> int:
        """The token produced for ``req``: its slot's entry of the tokens
        read back.  Every first and lockstep token is taken here."""
        return int(row)

    def _tick_body(self) -> None:
        """Refill free slots, then one lockstep decode."""
        tel = get_telemetry()
        for slot in range(self.batch):
            if self.active[slot] is None and self.pending:
                req = self.pending.pop(0)
                first = self._sample(req, self._fill_slot(slot, req))
                req.generated.append(first)
                tel.histogram("serving.ticks_to_first_token").record(
                    self.ticks - getattr(req, "_submit_tick", 0)
                )
                req._last_tok_tick = self.ticks
        occupied = [s for s in range(self.batch) if self.active[s] is not None]
        # sampled *after* refill: a request admitted and finished within one
        # tick still counts toward the occupancy it actually used
        tel.gauge("serving.slot_occupancy").set(len(occupied))
        tel.histogram("serving.slot_occupancy").record(len(occupied))
        if not occupied:
            return
        token = np.zeros((self.batch, 1), np.int32)
        for s in occupied:
            token[s, 0] = self.active[s].generated[-1]
        live = np.zeros(self.batch, bool)
        live[occupied] = True
        logits = self._decode(jnp.asarray(token), jnp.asarray(self.pos), live)
        tokens = self._read_tokens(self._sample_slots(logits, live))
        for s in occupied:
            req = self.active[s]
            self.pos[s] += 1
            nxt = self._sample(req, tokens[s])
            req.generated.append(nxt)
            tel.histogram("serving.ticks_per_token").record(
                self.ticks - getattr(req, "_last_tok_tick", self.ticks)
            )
            req._last_tok_tick = self.ticks
            if len(req.generated) >= req.max_new_tokens or self.pos[s] >= self.max_seq - 1:
                self._finish(req, "completed")
                self.active[s] = None

    def _on_step_failure(self, err: BaseException) -> None:
        self._fail_streak += 1
        self.retries += 1
        if self._fail_streak > self.max_retries:
            # Retry budget exhausted: terminate the in-flight requests with
            # their partial tokens, then recover — the queue keeps draining.
            for slot in range(self.batch):
                req = self.active[slot]
                if req is not None:
                    self._finish(
                        req,
                        "failed",
                        f"decode failed {self._fail_streak}x: {type(err).__name__}: {err}",
                    )
                    self.active[slot] = None
            self._fail_streak = 0
            self._cooldown = 0
            return
        self._cooldown = min(self.backoff_base * (2 ** (self._fail_streak - 1)), self.backoff_cap)
        warnings.warn(
            f"serving: decode tick failed ({type(err).__name__}: {err}); "
            f"retry {self._fail_streak}/{self.max_retries} after {self._cooldown} tick(s)",
            FallbackWarning,
            stacklevel=3,
        )

    def step(self) -> None:
        """One engine tick: expire deadlines, then refill + lockstep decode.

        A tick spent cooling down after a failed decode still advances the
        clock (deadlines keep expiring), so a wedged backend cannot stall
        requests forever.
        """
        self.ticks += 1
        tel = get_telemetry()
        self.tick_clock.advance(self.ticks)
        occupied = sum(a is not None for a in self.active)
        queue_depth = len(self.pending)
        tel.gauge("serving.queue_depth").set(queue_depth)
        tel.histogram("serving.queue_depth").record(queue_depth)
        wall0 = WALL.now()
        with tel.use_clock(self.tick_clock), tel.span(
            "serving.tick",
            tick=self.ticks,
            occupied=occupied,
            queue_depth=queue_depth,
        ) as sp:
            self._expire_deadlines()
            if self._cooldown > 0:
                self._cooldown -= 1
                sp.set("cooldown", True)
                tel.counter("serving.cooldown_ticks").add(1)
                return
            idx = _faults.next_index("serving.decode")
            try:
                if _faults.should_fire("launch", "serving.decode", idx, label="decode"):
                    raise _faults.InjectedFault(f"injected launch failure: serving.decode[{idx}]")
                self._tick_body()
            except Exception as err:
                sp.set("failed", type(err).__name__)
                self._on_step_failure(err)
                return
            finally:
                # wall duration goes to a histogram only — never into the
                # (tick-clocked, byte-identical) trace event stream
                tel.histogram("serving.tick_wall_us").record(WALL.now() - wall0)
            self._fail_streak = 0

    # -- draining ---------------------------------------------------------

    def _report(self) -> ServingReport:
        rep = ServingReport(ticks=self.ticks, retries=self.retries)
        for uid, req in self.done.items():
            rep.statuses[uid] = req.status
            if req.reason:
                rep.reasons[uid] = req.reason
            if req.status == "completed":
                rep.completed += 1
            elif req.status == "timed_out":
                rep.timed_out += 1
            elif req.status == "shed":
                rep.shed += 1
            elif req.status == "failed":
                rep.failed += 1
        return rep

    def run_until_done(self, max_ticks: int = 10_000) -> ServingReport:
        """Drain the engine; always return a :class:`ServingReport`.

        On hitting ``max_ticks`` no request is abandoned silently: in-flight
        requests are marked ``timed_out`` (partial ``generated`` preserved)
        and still-queued requests are marked ``shed``, all landing in
        ``self.done`` with explicit reasons.
        """
        tel = get_telemetry()
        with tel.use_clock(self.tick_clock), tel.span("serving.run", batch=self.batch):
            for _ in range(max_ticks):
                if not self.pending and all(a is None for a in self.active):
                    break
                self.step()
            else:
                for slot in range(self.batch):
                    req = self.active[slot]
                    if req is not None:
                        self._finish(req, "timed_out", f"engine out of ticks (max_ticks={max_ticks})")
                        self.active[slot] = None
                for req in self.pending:
                    self._finish(req, "shed", f"never scheduled within max_ticks={max_ticks}")
                self.pending = []
        rep = self._report()
        rep.telemetry = {
            "tick_wall_us": tel.histogram("serving.tick_wall_us").stats(),
            "ticks_to_first_token": tel.histogram("serving.ticks_to_first_token").stats(),
            "ticks_per_token": tel.histogram("serving.ticks_per_token").stats(),
            "slot_occupancy": tel.histogram("serving.slot_occupancy").stats(),
            "queue_depth": tel.histogram("serving.queue_depth").stats(),
        }
        return rep
