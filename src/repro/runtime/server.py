"""Production continuous-batching decode server.

The serving loop the Ember steady-state machine is graded under
(``benchmarks/bench_serving.py`` drives it open-loop):

* **Per-slot position counters** — the KV/MLA caches carry a vector
  ``len`` (B,), so every batch slot advances independently: admission,
  prefill and retirement are per-slot operations, never whole-batch
  drains.
* **Prompt-chunked prefill** — an admitted prompt is consumed in
  ``prefill_chunk``-token waves (:meth:`~repro.models.lm.LM.wave_step`)
  interleaved with the decode waves of the already-running slots.  A stack
  of GQA attention + MLP blocks feeds a wave's (slots, chunk) token block
  in one pass; other stacks replay it as a fused ``lax.scan`` of masked
  decode micro-steps.  Either way each token sees the same cache and takes
  the same reductions, so chunked prefill is **bit-identical** to
  whole-prompt prefill at any chunk size (tests/test_server.py asserts
  it), and only two traces exist: C=1 (pure decode) and C=prefill_chunk.
  ``serve_stats`` counts the prefill waves run as one pass
  (``prefill_passes``) and as a replay (``prefill_replays``).
* **Prioritized admission + slot recycling** — requests queue on a
  priority heap (lower ``Request.priority`` first, FIFO within a class);
  a slot that hits EOS / max-new / max-len retires *mid-wave*: its cache
  region is zeroed (:meth:`~repro.models.lm.LM.reset_slots`) and the next
  queued request is admitted in the same serving iteration, so a freed
  slot never idles a wave.
* **Token embeddings inside the wave** — ``wave_step`` embeds its tokens
  with a ``take`` on the embedding table, traced into the same jitted
  program as the blocks.  The server compiles no Ember program of its own.

Per-request service metrics (submit/admit/first-token/done wall-clock
stamps and per-token times) are recorded on the :class:`Request` itself —
what the open-loop bench aggregates into TTFT / per-token percentiles.

**Fault tolerance** (PR 7): the loop degrades per-request, never
per-process.

* **Input hardening** — prompts validate against the model vocab under
  ``index_policy`` ("strict" fails the request with a typed error,
  "clamp"/"drop" repair it and count).
* **SLO-aware admission** — a request carries a TTFT budget
  (``Request.deadline_s``, or the server-wide ``ttft_slo_s``): submit-time
  shedding predicts queue wait from the calibrated ``capacity_rps`` the
  serving bench measures, admission-time shedding predicts prefill time
  from the measured wave EWMA, and a request whose budget lapsed is
  retired with status ``expired`` — under overload the queue sheds
  instead of growing unboundedly.
* **Wave watchdog + bounded retry** — ``wave_deadline_s`` bounds the
  wave's dispatch; a wave that timed out or raised a typed fault is
  retried up to ``wave_retries`` times (the LM step itself runs once: it
  donates the caches) before failing ONLY the implicated requests; every
  other slot and all later waves proceed bit-identically to a fault-free
  run.

Each request ends in exactly one terminal ``status``: ``ok`` | ``shed`` |
``expired`` | ``failed``.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core.access_plan import INDEX_POLICIES
from .faults import EmberFault, WaveTimeout

#: terminal request statuses (Request.status ends as exactly one of these)
STATUSES = ("ok", "shed", "expired", "failed")


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # (L,) int32
    max_new_tokens: int = 16
    priority: int = 0               # lower serves first; FIFO within a class
    deadline_s: Optional[float] = None   # TTFT budget from submit (None: server SLO)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "queued"          # queued|active -> ok|shed|expired|failed
    error: Optional[str] = None     # typed failure detail (status != ok)
    # service metrics, stamped by the server (perf_counter seconds)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    token_times: list = dataclasses.field(default_factory=list)
    admitted_wave: Optional[int] = None
    finished_wave: Optional[int] = None


_EMPTY = np.zeros(0, np.int32)


class DecodeServer:
    def __init__(self, lm, params, *, batch_slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 prefill_chunk: int = 8,
                 index_policy: str = "strict",
                 capacity_rps=None,
                 capacity_warmup_waves: int = 5,
                 ttft_slo_s: Optional[float] = None,
                 wave_deadline_s: Optional[float] = None,
                 wave_retries: int = 1,
                 faults=None):
        assert index_policy in INDEX_POLICIES, index_policy
        self.lm = lm
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.eos = eos_id
        self.prefill_chunk = max(1, int(prefill_chunk))
        # --- fault-tolerance knobs -------------------------------------
        self.index_policy = index_policy
        # calibrated service capacity (requests/s at saturation — what
        # bench_serving.py's closed-loop calibration measures); drives the
        # submit-time predicted-wait shed.  None disables that check.
        # "auto" self-calibrates from the measured wave-time EWMA after
        # ``capacity_warmup_waves`` waves: capacity ≈ slots / (wave_s ×
        # avg waves-per-request) — no closed-loop bench number needed.
        self._capacity_auto = capacity_rps == "auto"
        self.capacity_rps = None if self._capacity_auto else capacity_rps
        self.capacity_warmup_waves = max(1, int(capacity_warmup_waves))
        self._req_wave_spans = 0    # Σ (finished_wave - admitted_wave + 1)
        self._req_span_count = 0
        # server-wide TTFT budget applied to requests without their own
        self.ttft_slo_s = ttft_slo_s
        self.wave_deadline_s = wave_deadline_s
        self.wave_retries = max(0, int(wave_retries))
        self.faults = faults            # chaos injector (site "wave" here)
        self._ewma_wave_s: Optional[float] = None   # measured wave time
        # prompt-validation bound: stub LMs expose `vocab`, real ones cfg
        self._vocab = getattr(lm, "vocab", None) or getattr(
            getattr(lm, "cfg", None), "vocab_size", None)
        self.queue: list = []           # (priority, submit seq, Request)
        self._seq = itertools.count()
        self.active: List[Optional[Request]] = [None] * batch_slots
        self._prompt_left: List[np.ndarray] = [_EMPTY] * batch_slots
        self._next_token = np.zeros(batch_slots, np.int32)
        self._pos = np.zeros(batch_slots, np.int64)   # host position mirror
        self.caches = lm.init_caches(batch_slots, max_len)
        # two traces total: C=1 decode waves, C=prefill_chunk prefill waves
        self._wave = jax.jit(lm.wave_step, donate_argnums=(3,))
        self._reset = jax.jit(lm.reset_slots, donate_argnums=(0,))
        self.waves = 0
        self._prefill_kind = "prefill_passes" \
            if lm.prefills_in_one_pass else "prefill_replays"
        self.serve_stats = {"waves": 0, "prefill_waves": 0,
                            "prefill_passes": 0, "prefill_replays": 0,
                            "decode_waves": 0, "admitted": 0, "finished": 0,
                            "slot_resets": 0, "queue_peak": 0,
                            "shed": 0, "expired": 0, "failed": 0,
                            "oob_prompt_tokens": 0, "wave_faults": 0,
                            "wave_retries": 0, "watchdog_timeouts": 0,
                            "capacity_rps_live": None}

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------

    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        if not self._harden_prompt(req):
            return                       # terminal: failed (typed error)
        if self._shed_at_submit(req):
            return                       # terminal: shed (predicted wait)
        heapq.heappush(self.queue, (req.priority, next(self._seq), req))
        self.serve_stats["queue_peak"] = max(self.serve_stats["queue_peak"],
                                             len(self.queue))

    def _terminate(self, req: Request, status: str,
                   error: Optional[str] = None):
        """Retire a request that never reached a slot (or leaves one):
        stamp its terminal status — the loop itself never dies for it."""
        req.status = status
        req.error = error
        req.done = True
        req.t_done = time.perf_counter()
        self.serve_stats[status if status != "ok" else "finished"] += 1

    def _harden_prompt(self, req: Request) -> bool:
        """Validate the prompt against the model vocab under
        ``index_policy``.  strict → the REQUEST fails (typed, terminal),
        clamp/drop → repair and count.  Returns False when terminal."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        req.prompt = prompt
        if self._vocab is None:
            return True
        bad = (prompt < 0) | (prompt >= self._vocab)
        nbad = int(bad.sum())
        if nbad == 0:
            return True
        if self.index_policy == "strict":
            self._terminate(
                req, "failed",
                error=f"MalformedAccessError: {nbad} prompt token(s) "
                      f"outside [0, {self._vocab})")
            return False
        self.serve_stats["oob_prompt_tokens"] += nbad
        if self.index_policy == "clamp":
            req.prompt = np.clip(prompt, 0, self._vocab - 1)
            return True
        req.prompt = prompt[~bad]        # drop
        if req.prompt.size == 0:
            self._terminate(req, "failed",
                            error="MalformedAccessError: prompt empty "
                                  "after dropping out-of-bounds tokens")
            return False
        return True

    def _deadline(self, req: Request) -> Optional[float]:
        return req.deadline_s if req.deadline_s is not None \
            else self.ttft_slo_s

    def _shed_at_submit(self, req: Request) -> bool:
        """Predicted-wait shed: with a calibrated service capacity, a
        request that would wait out its whole TTFT budget in the queue is
        shed NOW — the overload answer that keeps the queue bounded."""
        d = self._deadline(req)
        if d is None or not self.capacity_rps:
            return False
        predicted_wait = len(self.queue) / self.capacity_rps
        if predicted_wait > d:
            self._terminate(req, "shed",
                            error=f"predicted queue wait "
                                  f"{predicted_wait:.3f}s > budget {d:.3f}s")
            return True
        return False

    def _predict_ttft_s(self, req: Request) -> float:
        """Service-time part of the TTFT prediction at admission: prefill
        waves needed × the measured wave EWMA (0 until a wave has run —
        the cold server admits optimistically)."""
        if self._ewma_wave_s is None:
            return 0.0
        prefill_waves = max(
            1, -(-int(np.size(req.prompt)) // self.prefill_chunk))
        return prefill_waves * self._ewma_wave_s

    def _admit(self):
        """Fill every free slot from the priority heap — called at the top
        of each serving iteration AND right after mid-wave retirement, so a
        freed slot is refilled in the same iteration.  A popped request
        whose TTFT budget already lapsed (``expired``) or provably cannot
        make it (``shed``) is retired here, terminal, and the next queued
        request considered for the slot."""
        for i in range(self.slots):
            if self.active[i] is not None:
                continue
            while self.queue:
                _, _, req = heapq.heappop(self.queue)
                now = time.perf_counter()
                d = self._deadline(req)
                if d is not None:
                    waited = now - req.t_submit
                    if waited >= d:
                        self._terminate(req, "expired",
                                        error=f"TTFT budget {d:.3f}s "
                                              f"lapsed in queue")
                        continue
                    if waited + self._predict_ttft_s(req) > d:
                        self._terminate(
                            req, "shed",
                            error=f"predicted TTFT exceeds budget "
                                  f"{d:.3f}s at admission")
                        continue
                req.t_admit = now
                req.status = "active"
                req.admitted_wave = self.waves
                self.active[i] = req
                # leave >=1 position of room for generated tokens
                self._prompt_left[i] = np.asarray(
                    req.prompt, np.int32).reshape(-1)[:self.max_len - 1]
                self._pos[i] = 0
                self.serve_stats["admitted"] += 1
                break

    def _finish(self, i: int, req: Request, retired: np.ndarray,
                status: str = "ok", error: Optional[str] = None):
        req.status = status
        if error is not None:
            req.error = error
        req.done = True
        req.t_done = time.perf_counter()
        req.finished_wave = self.waves
        if req.admitted_wave is not None:
            # waves this request occupied a slot — the span the auto
            # capacity estimate divides the wave throughput by
            self._req_wave_spans += max(
                1, req.finished_wave - req.admitted_wave + 1)
            self._req_span_count += 1
        retired[i] = True
        self.serve_stats[status if status != "ok" else "finished"] += 1

    def _recycle(self, retired: np.ndarray):
        """Mid-wave slot recycling: zero the retired slots' cache state and
        admit from the queue into them immediately."""
        if not retired.any():
            return
        self.caches = self._reset(self.caches, jnp.asarray(~retired))
        self.serve_stats["slot_resets"] += int(retired.sum())
        for i in np.where(retired)[0]:
            self.active[i] = None
            self._prompt_left[i] = _EMPTY
            self._pos[i] = 0
        self._admit()

    # ------------------------------------------------------------------
    # Wave loop
    # ------------------------------------------------------------------

    def step(self) -> int:
        """One serving iteration: admit → one wave (chunked prefill and/or
        decode) → retire + recycle + same-iteration admit.  Returns the
        number of active slots afterwards.

        Spans (:mod:`repro.tracing`), keyed by the wave number: ``wave``
        around the iteration, with ``wave.admit``, ``wave.dispatch``,
        ``wave.sync`` (the host waiting on the wave's argmax) and
        ``wave.emit`` inside it."""
        with tracing.span("wave", self.waves):
            retired = np.zeros(self.slots, bool)
            with tracing.span("wave.admit"):
                wave = self._assemble(retired)
            if wave is None:
                self._recycle(retired)
                return self._n_active()
            c, tokens, lens, emits = wave
            with tracing.span("wave.dispatch"):
                logits, counts, t0 = self._run_wave(tokens, lens, retired)
            if logits is None:
                return self._n_active()
            with tracing.span("wave.sync"):
                nxt = jnp.argmax(logits[:, 0], axis=-1)
                if counts is None:
                    nxt = np.asarray(nxt)
                else:
                    nxt, counts = jax.device_get((nxt, counts))
                    self._count_experts(counts)
            # the wave's time through its sync, not just its dispatch
            dt = time.perf_counter() - t0
            self._ewma_wave_s = dt if self._ewma_wave_s is None else \
                0.7 * self._ewma_wave_s + 0.3 * dt
            with tracing.span("wave.emit"):
                self._emit(c, lens, nxt, emits, retired)
            return self._n_active()

    def _n_active(self) -> int:
        return sum(r is not None for r in self.active)

    def _assemble(self, retired: np.ndarray):
        """Admit, then lay out this wave's tokens: ``(chunk, tokens, lens,
        emits)``, or None when no slot has a token to feed (slots that ran
        out of cache room are marked in ``retired``)."""
        self._admit()
        if not any(r is not None for r in self.active):
            return None
        c = self.prefill_chunk \
            if any(p.size for p in self._prompt_left) else 1
        tokens = np.zeros((self.slots, c), np.int32)
        lens = np.zeros(self.slots, np.int32)
        emits = np.zeros(self.slots, bool)   # slot emits a token this wave
        for i, req in enumerate(self.active):
            if req is None:
                continue
            room = self.max_len - int(self._pos[i])
            left = self._prompt_left[i]
            if left.size:
                n = min(left.size, c, room)
                if n == 0:      # no cache room left mid-prompt: truncated
                    self._finish(i, req, retired)
                    continue
                tokens[i, :n] = left[:n]
                lens[i] = n
                self._prompt_left[i] = left[n:]
                emits[i] = self._prompt_left[i].size == 0
            else:
                if room <= 0:   # cannot place another token
                    self._finish(i, req, retired)
                    continue
                tokens[i, 0] = self._next_token[i]
                lens[i] = 1
                emits[i] = True
        if lens.sum() == 0:
            return None
        return c, tokens, lens, emits

    def _run_wave(self, tokens: np.ndarray, lens: np.ndarray,
                  retired: np.ndarray):
        """The guarded wave body: the LM step under the watchdog deadline,
        retried after a typed fault.  Returns the wave's (unsynced)
        logits, its expert counters (None for a model without experts)
        and the time its last attempt started, or
        ``(None, None, None)`` once the retries are spent: the slots it
        served have then failed and recycled."""
        tokens_j, lens_j = jnp.asarray(tokens), jnp.asarray(lens)
        t0 = time.perf_counter()
        lm_done = False     # the LM wave donates its caches: NEVER re-run
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.fire("wave", wave=self.waves)
                if not lm_done:
                    logits, self.caches, *counts = self._wave(
                        self.params, tokens_j, lens_j, self.caches)
                    lm_done = True
                if self.wave_deadline_s is not None:
                    el = time.perf_counter() - t0
                    if el > self.wave_deadline_s:
                        raise WaveTimeout(
                            f"wave {self.waves} took {el * 1e3:.1f}ms > "
                            f"deadline {self.wave_deadline_s * 1e3:.1f}ms")
                return logits, (counts[0] if counts else None), t0
            except EmberFault as e:
                # typed faults only: anything else is a bug and propagates
                self.serve_stats["wave_faults"] += 1
                if isinstance(e, WaveTimeout):
                    self.serve_stats["watchdog_timeouts"] += 1
                if attempt >= self.wave_retries:
                    # fail ONLY the implicated requests (the slots served
                    # by this wave); their slots recycle, the loop lives
                    err = f"{type(e).__name__}: {e}"
                    for i, req in enumerate(self.active):
                        if req is None or retired[i]:
                            continue
                        self._finish(i, req, retired, status="failed",
                                     error=err)
                    self._recycle(retired)
                    return None, None, None
                attempt += 1
                self.serve_stats["wave_retries"] += 1
                t0 = time.perf_counter()   # the retry gets a fresh budget

    def _count_experts(self, counts: np.ndarray) -> None:
        """A wave's expert counters (``LM.wave_step``'s third value) into
        ``serve_stats``: token-expert assignments on held experts, and
        (layer, micro-step, held expert) triples that got a token."""
        for name, n in zip(("moe_held_assignments", "moe_experts_touched"),
                           counts):
            self.serve_stats[name] = self.serve_stats.get(name, 0) + int(n)

    def _emit(self, c: int, lens: np.ndarray, nxt: np.ndarray,
              emits: np.ndarray, retired: np.ndarray) -> None:
        """After the wave: count it, expire lapsed slots, append each
        emitting slot's token, retire finished requests and recycle."""
        self._pos += lens
        self.waves += 1
        self.serve_stats["waves"] += 1
        if c > 1:
            self.serve_stats["prefill_waves"] += 1
            self.serve_stats[self._prefill_kind] += 1
        else:
            self.serve_stats["decode_waves"] += 1
        now = time.perf_counter()
        # mid-wave expiry: a slot still waiting on its first token whose
        # TTFT budget lapsed during service retires here (terminal), so an
        # overloaded wave never holds dead slots
        for i, req in enumerate(self.active):
            if req is None or retired[i] or req.t_first is not None:
                continue
            d = self._deadline(req)
            if d is not None and now - req.t_submit > d:
                self._finish(i, req, retired, status="expired",
                             error=f"TTFT budget {d:.3f}s lapsed in service")
        for i, req in enumerate(self.active):
            if req is None or retired[i] or not emits[i]:
                continue
            tok = int(nxt[i])
            req.out.append(tok)
            req.token_times.append(now)
            if req.t_first is None:
                req.t_first = now
            self._next_token[i] = tok
            if (self.eos is not None and tok == self.eos) or \
                    len(req.out) >= req.max_new_tokens or \
                    int(self._pos[i]) >= self.max_len:
                self._finish(i, req, retired)
        self._recycle(retired)
        # after the finish pass, so a drive whose requests all retire on
        # the final wave still arms the estimate before draining
        self._update_capacity()

    def _update_capacity(self) -> None:
        """Live capacity estimate under ``capacity_rps="auto"``: each wave
        serves up to ``slots`` requests concurrently, and a finished
        request occupied its slot for its measured wave span, so sustained
        throughput ≈ slots / (wave_s × avg waves-per-request).  Armed only
        after the warmup wave count (cold-compile waves would poison the
        EWMA) and at least one finished request."""
        if not self._capacity_auto or self._ewma_wave_s is None or \
                self.waves < self.capacity_warmup_waves or \
                not self._req_span_count:
            return
        avg_span = self._req_wave_spans / self._req_span_count
        est = self.slots / (self._ewma_wave_s * avg_span)
        self.capacity_rps = est
        self.serve_stats["capacity_rps_live"] = round(est, 2)

    def run_until_drained(self, max_steps: int = 100_000):
        steps = 0
        while (self.queue or
               any(r is not None for r in self.active)) and \
                steps < max_steps:
            self.step()
            steps += 1
        return steps
