"""Request/Completion API + FCFS admission scheduler for continuous batching.

The scheduler is deliberately dumb and deterministic: requests are admitted
strictly in submission order, each as soon as (a) its arrival step has been
reached on the engine clock and (b) a KV-cache slot is free.  The engine
clock is the decode-step counter, so synthetic staggered-arrival workloads
replay bit-identically — the property every serving test here leans on.

Layering (see ROADMAP.md §Serving and docs/serving.md):  scheduler (this
file, admission *order*) -> kv_cache (slot/block KV residency, device
placement) -> engine (ContinuousEngine, the jit-once fused step).  Under a
device mesh the scheduler's contract is unchanged — the scheduler decides
*who* is admitted next; the engine + pool decide *where* (least-loaded
device's slot range), so placement never reorders admissions.

``PriorityScheduler`` adds the SLA control plane on the same deterministic
clock: class-aware admission (interactive over batch), an aging bound so
batch traffic cannot starve, and watermark-based shedding of batch
backlog under overload.  Its rank rule is deliberately step-independent
(``interactive h outranks batch b  iff  h.arrival_step < b.arrival_step +
aging_steps``) so the engine can reuse the *same* rule for preemption
victim eligibility without admit/preempt livelock: the relative order of
two requests never changes as the clock advances.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import numpy as np

# request classes the SLA control plane understands.  'interactive' is the
# latency-sensitive class (chat turns: short budgets, TTFT-judged);
# 'batch' is throughput traffic (eval/summarization sweeps: long budgets,
# preemptible, sheddable under overload).
REQUEST_CLASSES = ("interactive", "batch")

# closed set of completion verdicts.  'length' = decode budget exhausted,
# 'stop' = stop token hit, 'rejected' = shed before admission, 'failed' =
# fault-recovery retry budget exhausted (the request's fault record is in
# the engine event log).  Validated at Completion construction so a typo'd
# or novel reason fails at the producer, never silently at a consumer.
FINISH_REASONS = ("length", "stop", "rejected", "failed")


def pad_to_grid(tokens, grid: int) -> np.ndarray:
    """Right-pad a prompt to the next multiple of the chunk grid.

    This is the bucketing rule of the fused serving step: every prompt is
    quantized to the chunk grid at intake, so the engine's per-tick shape is
    always (num_slots, chunk) and one compilation covers every prompt-length
    mix.  Padding is bounded by grid-1 tokens and the pad tokens are never
    computed on — the fused step masks lanes >= the true remaining length
    (they neither enter the cache nor advance recurrent state).
    """
    t = np.asarray(tokens, np.int32).reshape(-1)
    if grid <= 1:
        return t
    rem = (-t.shape[0]) % grid
    return np.concatenate([t, np.zeros(rem, np.int32)]) if rem else t


@dataclasses.dataclass
class Request:
    """One generation request.  ``tokens`` is the prompt, shape (prompt_len,).

    ``extras`` carries per-request modality stubs without a batch dim
    (``frames`` for encdec, ``patches`` for vlm); the engine adds the batch
    axis at prefill.  ``arrival_step`` stamps when the request becomes
    visible on the engine's decode-step clock (0 = already waiting).
    ``padded_tokens`` is stamped by a chunk-gridded scheduler at submit
    (see ``pad_to_grid``); engines fall back to padding at admission when
    it is absent or on a different grid.
    """

    tokens: np.ndarray
    max_new_tokens: int = 16
    temperature: Optional[float] = None  # None -> engine default
    stop_token: Optional[int] = None
    arrival_step: int = 0
    extras: dict = dataclasses.field(default_factory=dict)
    id: int = -1  # assigned by the scheduler on submit
    padded_tokens: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    # submit-time prefix-cache hint: how many leading prompt tokens were
    # already indexed when the request entered the queue (telemetry only —
    # admission re-runs the authoritative lookup against the cache state at
    # admit time, which later finishes/evictions will have changed)
    prefix_hint: int = 0
    # SLA class ('interactive' | 'batch').  FCFS ignores it; the
    # PriorityScheduler ranks on it and the engine's preemption/shedding
    # paths only ever target 'batch' requests.
    req_class: str = "interactive"

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[-1])


@dataclasses.dataclass
class Completion:
    """A finished request plus its serving telemetry (steps = engine clock)."""

    request_id: int
    prompt_tokens: np.ndarray
    new_tokens: np.ndarray
    finish_reason: str  # one of FINISH_REASONS
    arrival_step: int
    admit_step: int  # -1 when rejected (never admitted)
    first_token_step: int  # -1 when rejected
    finish_step: int
    admit_time: float
    first_token_time: float
    finish_time: float
    req_class: str = "interactive"
    preemptions: int = 0  # times this request was evicted and later resumed
    # (n_new, vocab) held logits each new token was chosen from, kept only
    # by an engine built with record_logits=True
    logits: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.finish_reason not in FINISH_REASONS:
            raise ValueError(
                f"unknown finish_reason {self.finish_reason!r}; expected one "
                f"of {FINISH_REASONS}"
            )

    @property
    def tokens(self) -> np.ndarray:
        """Full sequence (prompt + generated), the static-engine layout."""
        return np.concatenate([self.prompt_tokens, self.new_tokens])

    @property
    def ttft_s(self) -> float:
        return self.first_token_time - self.admit_time

    @property
    def latency_s(self) -> float:
        return self.finish_time - self.admit_time

    # --- arrival-anchored step-clock SLA fields -------------------------
    # ttft_s above measures from *admit*, which hides queue wait entirely —
    # exactly the quantity an overloaded system lies about.  The step-clock
    # fields anchor on arrival_step and are deterministic under replay
    # (wall-clock fields are kept as-is for compatibility).

    @property
    def queue_wait_steps(self) -> int:
        """Engine steps spent waiting for admission (arrival -> admit)."""
        if self.admit_step < 0:
            return self.finish_step - self.arrival_step  # rejected: wait-to-verdict
        return self.admit_step - self.arrival_step

    @property
    def ttft_steps(self) -> int:
        """Arrival -> first generated token, on the engine step clock.
        -1 for rejected requests (no token was ever produced)."""
        if self.first_token_step < 0:
            return -1
        return self.first_token_step - self.arrival_step

    @property
    def tpot_steps(self) -> float:
        """Mean steps per generated token after the first (first token ->
        finish).  Exactly 1.0 for an uninterrupted decode; preemption gaps
        and re-prefill ticks inflate it.  0.0 when < 2 tokens."""
        n = int(np.asarray(self.new_tokens).shape[0])
        if n < 2:
            return 0.0
        return (self.finish_step - self.first_token_step) / (n - 1)


class FCFSScheduler:
    """First-come-first-served admission.  The head of the queue blocks —
    a later-arriving short request never jumps an earlier long one, which
    keeps admission order (and therefore slot assignment) deterministic.

    With ``chunk_grid`` > 0 the scheduler buckets waiting prompts to the
    fused step's chunk grid at submit (``pad_to_grid``): intake padding is
    bounded by grid-1 tokens per request and the engine's per-tick shape is
    independent of the prompt-length mix, so the fused step compiles once.

    With a ``prefix_cache`` bound (the engine passes its own), submit stamps
    each queued request's ``prefix_hint`` — the indexed prefix length at
    submit time, via the stamp-free ``match_len`` so queue traffic never
    perturbs LRU order.  The hint is telemetry (demos print it; operators
    see sharing potential at intake); admission re-runs the authoritative
    lookup, since the cache keeps changing while the request waits.
    """

    def __init__(self, chunk_grid: int = 0, prefix_cache=None):
        self.chunk_grid = int(chunk_grid)
        self.prefix_cache = prefix_cache
        self._queue: deque[Request] = deque()
        self._next_id = 0
        self._pad_tokens = 0  # total intake padding (bucketing overhead)

    def submit(self, req: Request) -> int:
        """Enqueue a *copy* of ``req`` and return its id.

        Submit is side-effect-free on the caller's object: id assignment and
        chunk-grid bucketing land on the queued copy only.  (The old in-place
        mutation meant re-submitting one workload list across the static
        oracle, engine resets and bench reps carried hidden state — and a
        stale ``padded_tokens`` from a different chunk grid was only caught
        by the ``% chunk`` fallback in the engine's admission path.)
        """
        queued = self._prepare(req)
        self._enqueue(queued)
        return queued.id

    def _prepare(self, req: Request) -> Request:
        """Validate + copy + bucket a submission (shared by all policies)."""
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request needs max_new_tokens >= 1, got {req.max_new_tokens} "
                "(the engine always decodes at least one token per admission)"
            )
        if req.req_class not in REQUEST_CLASSES:
            raise ValueError(
                f"unknown req_class {req.req_class!r}; expected one of "
                f"{REQUEST_CLASSES}"
            )
        rid = req.id if req.id >= 0 else self._next_id
        self._next_id = max(self._next_id, rid) + 1
        queued = dataclasses.replace(req, id=rid)
        # never trust a padded_tokens stamped by some other scheduler's grid
        queued.padded_tokens = (
            pad_to_grid(queued.tokens, self.chunk_grid) if self.chunk_grid else None
        )
        if self.chunk_grid:
            self._pad_tokens += int(queued.padded_tokens.shape[0]) - queued.prompt_len
        if self.prefix_cache is not None:
            queued.prefix_hint = self.prefix_cache.match_len(queued.tokens)
        return queued

    def _enqueue(self, queued: Request) -> None:
        self._queue.append(queued)

    def requeue_front(self, req: Request) -> None:
        """Put an already-prepared request back at the head of its queue.

        The engine's preemption path uses this: the victim keeps its id,
        padding and arrival_step (its place in time), and is the next
        candidate of its class — so a preempted request is never overtaken
        by a later submission of the same class, which is what makes the
        preemption trace replay-deterministic.
        """
        self._queue.appendleft(req)

    def next_ready_step(self) -> Optional[int]:
        """Earliest arrival_step over all queued requests, or None if empty.

        The engine's idle fast-forward jumps its step clock here when no
        slot is live: nothing observable can happen on the skipped ticks
        (no arrivals, no admissions, no decodes), so the event trace is
        identical to burning them one by one.
        """
        if not self._queue:
            return None
        # FCFS is head-blocking: nothing is admissible before the head
        # arrives, even if a later submission has an earlier arrival_step.
        return self._queue[0].arrival_step

    def poll_shed(self, step: int, live_units: int, unit_fn) -> list[Request]:
        """Overload shedding hook, called by the engine each admission pass.
        FCFS never sheds; the PriorityScheduler implements the watermark."""
        return []

    @property
    def intake_padding(self) -> int:
        """Total pad tokens added by bucketing (<= (grid-1) per request)."""
        return self._pad_tokens

    def peek_ready(self, step: int) -> Optional[Request]:
        """Head of the queue if it has arrived by engine step ``step``,
        without popping — admission checks resources (free blocks) first."""
        if self._queue and self._queue[0].arrival_step <= step:
            return self._queue[0]
        return None

    def pop_ready(self, step: int) -> Optional[Request]:
        """Head of the queue if it has arrived by engine step ``step``."""
        if self._queue and self._queue[0].arrival_step <= step:
            return self._queue.popleft()
        return None

    def has_pending(self) -> bool:
        return bool(self._queue)

    def __len__(self) -> int:
        return len(self._queue)


class PriorityScheduler(FCFSScheduler):
    """Class-aware admission with an aging bound and overload shedding.

    Two FCFS queues, one per request class.  When both heads have arrived,
    the *rank rule* picks the winner:

        interactive head h outranks batch head b
            iff  h.arrival_step < b.arrival_step + aging_steps

    i.e. interactive goes first unless the batch head has already waited
    ``aging_steps`` longer than the interactive head has existed — the
    starvation bound: every interactive request admitted before a given
    batch request arrived strictly less than ``aging_steps`` after it.
    The rule compares only arrival steps (never the current clock), so the
    relative order of two requests is a constant of the run.  The engine
    uses the *same* rule to decide which live batch slots an interactive
    head may preempt; sharing one total order is what rules out the
    admit/preempt livelock (preempt a victim, victim re-queues, victim
    outranks the head, victim re-admits, preempt again, ...).

    Shedding (``shed_backlog`` > 0, units = blocks under a paged pool,
    slots under a slab pool): each admission pass the engine reports the
    live reservation and a per-request footprint function; arrived batch
    backlog beyond the watermark is rejected (``finish_reason='rejected'``)
    head-ordered, so the survivor set is deterministic.  Interactive
    requests and preempted-then-requeued requests are never shed — a
    request the engine already spent prefill on is always allowed back.
    """

    def __init__(self, chunk_grid: int = 0, prefix_cache=None,
                 aging_steps: int = 64, shed_backlog: int = 0):
        super().__init__(chunk_grid, prefix_cache)
        if aging_steps < 1:
            raise ValueError(f"aging_steps must be >= 1, got {aging_steps}")
        self.aging_steps = int(aging_steps)
        self.shed_backlog = int(shed_backlog)
        self._queues: dict[str, deque[Request]] = {
            c: deque() for c in REQUEST_CLASSES
        }
        self._resumed: set[int] = set()  # ids requeued by preemption
        self.shed_count = 0

    def _enqueue(self, queued: Request) -> None:
        self._queues[queued.req_class].append(queued)

    def requeue_front(self, req: Request) -> None:
        self._resumed.add(req.id)
        self._queues[req.req_class].appendleft(req)

    def outranks(self, interactive_arrival: int, batch_arrival: int) -> bool:
        """The step-independent rank rule (see class docstring)."""
        return interactive_arrival < batch_arrival + self.aging_steps

    def _pick_class(self, step: int) -> Optional[str]:
        heads = {}
        for c in REQUEST_CLASSES:
            q = self._queues[c]
            if q and q[0].arrival_step <= step:
                heads[c] = q[0]
        if len(heads) == 2:
            i, b = heads["interactive"], heads["batch"]
            return ("interactive"
                    if self.outranks(i.arrival_step, b.arrival_step)
                    else "batch")
        return next(iter(heads), None)

    def peek_ready(self, step: int) -> Optional[Request]:
        c = self._pick_class(step)
        return self._queues[c][0] if c else None

    def pop_ready(self, step: int) -> Optional[Request]:
        c = self._pick_class(step)
        if c is None:
            return None
        req = self._queues[c].popleft()
        self._resumed.discard(req.id)
        return req

    def has_pending(self) -> bool:
        return any(self._queues.values())

    def __len__(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def next_ready_step(self) -> Optional[int]:
        # both class heads are admissible candidates, so the next
        # observable event is the earlier of the two head arrivals
        heads = [q[0].arrival_step for q in self._queues.values() if q]
        return min(heads) if heads else None

    def poll_shed(self, step: int, live_units: int, unit_fn) -> list[Request]:
        """Shed arrived batch backlog beyond the watermark.

        ``live_units`` is the engine's current reservation (blocks in
        reserve under paging, occupied slots under slab); ``unit_fn(req)``
        prices a queued request in the same units.  Demand accumulates
        head-ordered: live + arrived interactive + arrived batch in queue
        order; the first batch request that pushes demand past
        ``shed_backlog`` is shed, as is every arrived batch request after
        it that would too.  Scanning stops at the first not-yet-arrived
        request per queue (so idle fast-forward stays sound: a skipped
        tick can never have shed anything).
        """
        if self.shed_backlog <= 0:
            return []
        demand = live_units
        for r in self._queues["interactive"]:
            if r.arrival_step > step:
                break
            demand += unit_fn(r)
        kept: deque[Request] = deque()
        shed: list[Request] = []
        arrived_zone = True
        for r in self._queues["batch"]:
            if arrived_zone and r.arrival_step > step:
                arrived_zone = False
            if not arrived_zone:
                kept.append(r)
                continue
            need = unit_fn(r)
            if r.id in self._resumed:
                # preempted work is admitted debt, never shed
                demand += need
                kept.append(r)
            elif demand + need > self.shed_backlog:
                shed.append(r)
                self.shed_count += 1
            else:
                demand += need
                kept.append(r)
        self._queues["batch"] = kept
        return shed
