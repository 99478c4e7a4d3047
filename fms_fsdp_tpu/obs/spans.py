"""Host spans of the serving engine, on the profiler's clock, and the
engine's step log, on the host's.

``span("decode", step=41, live=8)`` enters
``jax.profiler.TraceAnnotation("serve/decode", step=41, live=8)``: while a
``jax.profiler`` session runs, the span lands in the same ``.xplane.pb``
and on the same clock as the device's events, which is what lets an idle
gap of the chip be put down to a phase of ``ServingEngine.step()``. With
no session the annotation checks one flag and returns, so "tracing off"
is "no profiler session": there is no switch or sink here. The keyword
counts come back as the event's stats (``jax.profiler.ProfileData``:
``dict(event.stats)``).

A ``TraceAnnotation`` takes its counts when it is entered. A count that
is known only when the work ends goes on ``done``: a zero-length
``serve/<name>.done`` span entered as the last thing inside its parent.

**The step log.** The same call also times itself on the host
(``time.perf_counter_ns``) into the record of the ``step()`` that is
open on this thread, session or none: ``TIMED`` says which field a
span's time is added to, ``COUNTED`` which of its counts go into the
record. The engine opens a record at the top of ``step()``
(``StepLog.open``) and closes it at the bottom; a span entered while
none is open (``submit``, a collect outside a step) is annotated and
not recorded. The open record is the thread's, so the adapter's spans
find it without being handed anything, and two engines stepped in turn,
or on two threads, never write into each other's. ``FIELDS`` is a
record; ``StepLog`` keeps the last ``CAPACITY`` of them.

docs/observability.md "Tracing a serving replica" names every span and
every field.
"""

import threading
import time
from array import array
from collections import deque

PREFIX = "serve/"

# why an admission loop ended (``admit_stopped``), by the code a record
# keeps: nobody queued; no slot free; the head of the queue did not pass
# ``can_admit``; ``max_prefill_per_step`` reached; the engine drains
ADMIT_STOPPED = ("queue_empty", "no_slot", "no_pages", "budget", "draining")

# one record, one ``step()``. ``*_us``: host microseconds under the spans
# of ``TIMED``, summed over the step; ``t``: the engine's clock at entry
FIELDS = (
    "step", "t", "wall_us",
    "expire_us", "admit_us", "prefill_us", "grow_us", "decode_us",
    "publish_us",
    "prefill_dispatch_us", "prefill_write_us", "prefill_sample_us",
    "table_us", "dispatch_us", "wait_us", "commit_us",
    "queued", "busy",  # at entry
    "admitted", "busy_after_admit", "admit_stopped",
    "padded_tokens", "computed_tokens", "built",  # the step's prefills
    # of them, in a family whose positions choose their context (minicpm_sala):
    # the positions that chose and the blocks they chose
    "chose_tokens", "chosen_blocks",
    # of them, in a family whose prefill stops half way (phi4flash): the
    # positions the first half of the stack computed and those the second
    "self_positions", "cross_positions",
    "live", "kv_tokens",  # of the decode step dispatched
    # the blocks the ragged paged kernel's loops walk in it, for one layer
    # that reads the pages (ops/paged_attention.py); 0 without that kernel
    "attn_blocks",
    "live_chose",  # its live streams that chose their context
    "tokens",  # decode tokens committed in the step
    "pages_in_use",  # at publish
    "hbm_in_use", "hbm_largest_free",  # -1: not sampled
    "slow",  # 1: the slow-step rule logged it
)
SLOT = {name: i for i, name in enumerate(FIELDS)}
_FLOATS = frozenset(SLOT[n] for n in FIELDS if n == "t" or n.endswith("_us"))
_ABSENT = (SLOT["hbm_in_use"], SLOT["hbm_largest_free"])
_BLANK = array("d", [0.0] * len(FIELDS))
for _i in _ABSENT:
    _BLANK[_i] = -1.0

# span -> the field its host time is added to
TIMED = {
    name: SLOT[f] for name, f in {
        "step": "wall_us",
        "expire": "expire_us",
        "admit": "admit_us",
        "prefill": "prefill_us",
        "prefill_chunk": "prefill_us",
        "prefill.land": "prefill_us",
        "grow": "grow_us",
        "decode": "decode_us",
        "publish": "publish_us",
        "prefill.dispatch": "prefill_dispatch_us",
        "prefill.write_pages": "prefill_write_us",
        "prefill.write_state": "prefill_write_us",
        "prefill.sample": "prefill_sample_us",
        "decode.table": "table_us",
        "decode.dispatch": "dispatch_us",
        "decode.wait": "wait_us",
        "decode.commit": "commit_us",
    }.items()
}
# span -> the counts of it that are added to the field of the same name
COUNTED = {
    name: tuple((k, SLOT[k]) for k in keys) for name, keys in {
        "prefill": ("padded_tokens",),
        "prefill.done": (
            "computed_tokens", "chose_tokens", "chosen_blocks",
            "self_positions", "cross_positions"),
        "prefill.dispatch": ("built",),
        "decode": ("live", "kv_tokens", "attn_blocks"),
        "decode.dispatch": ("live_chose",),
        "admit.done": ("admitted",),
        "decode.commit.done": ("tokens",),
    }.items()
}
# span -> (the slot its time goes to or None, its counted counts)
_PLAN = {
    name: (TIMED.get(name), COUNTED.get(name, ()))
    for name in {*TIMED, *COUNTED}
}
_now = time.perf_counter_ns


class _Open(threading.local):
    rec = None  # the record of the step() this thread is inside


_open = _Open()


class _Timed:
    """An entered annotation whose host time goes into ``rec[slot]``."""

    __slots__ = ("ann", "rec", "slot", "t0")

    def __init__(self, ann, rec, slot):
        self.ann = ann
        self.rec = rec
        self.slot = slot

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = _now()

    def __exit__(self, kind, value, tb):
        self.rec[self.slot] += (_now() - self.t0) * 1e-3
        self.ann.__exit__(kind, value, tb)


def span(name: str, **counts):
    """Context manager: the host span ``serve/<name>`` carrying
    ``counts`` (numbers or short strings); inside a ``step()`` it also
    adds its host time and its counted counts to the step's record."""
    # imported here: fms_fsdp_tpu.obs is also imported by processes that
    # must not load jax (supervisors, the smoke's parent)
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(PREFIX + name, **counts)
    rec = _open.rec
    plan = None if rec is None else _PLAN.get(name)
    if plan is None:
        return ann
    slot, counted = plan
    for key, at in counted:
        rec[at] += counts.get(key, 0)  # a family sends the counts it has
    return ann if slot is None else _Timed(ann, rec, slot)


def done(name: str, **counts) -> None:
    """The counts of span ``name`` that were known only at its end, as a
    zero-length ``serve/<name>.done`` span."""
    with span(name + ".done", **counts):
        pass


def as_dict(rec) -> dict:
    """A record by field name: whole numbers as ints, ``admit_stopped``
    by its name, a memory figure that was not sampled left out."""
    out = {}
    for i, name in enumerate(FIELDS):
        v = rec[i]
        if i in _ABSENT and v < 0:
            continue
        out[name] = v if i in _FLOATS else int(v)
    out["admit_stopped"] = ADMIT_STOPPED[out["admit_stopped"]]
    return out


class StepLog:
    """The records of an engine's last ``CAPACITY`` steps, oldest first
    (iterating and indexing give ``as_dict`` records), the one that is
    open, and how far back the running profiler session has been given
    them (``unwritten``)."""

    CAPACITY = 8192  # a 45 s window of any benchmark cell is under 2700
    # earlier records a step hands a session: as many as fit in the
    # host's slack, which is the step's own wait for the device, at
    # REPLAY_US of host time a record (one costs 24-38 us), no fewer than
    # REPLAY_FLOOR and no more than REPLAY. A traced 3 s has to carry the
    # 42 s before it, and the end of a window that reads its offer can
    # hold as few as 16 steps against 1900 before them
    REPLAY = 256
    REPLAY_FLOOR = 64
    REPLAY_US = 60.0
    # the slow-step rule: over both, and nothing was built in the step
    SLOW_S = 1.0
    SLOW_TIMES = 2.0

    def __init__(self, capacity: int = CAPACITY):
        self.ring = deque(maxlen=capacity)
        self.closed = 0  # records ever closed
        # the oldest record (counted from the first ever closed) that the
        # running session holds; None while no session runs
        self._given = None

    def __len__(self):
        return len(self.ring)

    def __iter__(self):
        return map(as_dict, self.ring)

    def __getitem__(self, i):
        return as_dict(self.ring[i])

    def open(self, step: int, t: float, queued: int, busy: int):
        """Open the record of ``step`` on this thread -> the record (an
        ``array`` of ``FIELDS``; the engine writes what no span carries)."""
        rec = _BLANK[:]
        rec[SLOT["step"]], rec[SLOT["t"]] = step, t
        rec[SLOT["queued"]], rec[SLOT["busy"]] = queued, busy
        _open.rec = rec
        return rec

    def close(self, rec) -> None:
        _open.rec = None
        self.ring.append(rec)
        self.closed += 1

    def is_slow(self, rec) -> bool:
        """The rule of a step that stood still: nothing was built in it
        (a build's wall is a compile), it took over ``SLOW_S`` and over
        ``SLOW_TIMES`` the longest earlier step in the ring that
        computed at least as many prefill positions, built nothing and
        was not itself slow. A step with no such earlier step has
        nothing to be held against (the first prefill of its size) and
        is not judged. Marks the record."""
        wall, built = SLOT["wall_us"], SLOT["built"]
        if rec[built] or rec[wall] <= self.SLOW_S * 1e6:
            return False
        computed, slow = SLOT["computed_tokens"], SLOT["slow"]
        longest = max(
            (r[wall] for r in self.ring
             if r is not rec and not r[built] and not r[slow]
             and r[computed] >= rec[computed]),
            default=None)
        if longest is None or rec[wall] <= self.SLOW_TIMES * longest:
            return False
        rec[slow] = 1
        return True

    def tokens_per_s(self) -> float:
        """Tokens committed over the engine's clock across the steps the
        ring holds: those of every step but the newest, over the time
        from the oldest step's entry to the newest's."""
        if len(self.ring) < 2:
            return 0.0
        t, tok = SLOT["t"], SLOT["tokens"]
        elapsed = self.ring[-1][t] - self.ring[0][t]
        tokens = sum(r[tok] for r in self.ring) - self.ring[-1][tok]
        return tokens / elapsed if elapsed > 0 else 0.0

    def unwritten(self):
        """What the step that just closed hands the running profiler
        session, as ``as_dict`` records: its own record, then those of
        the ring that this session has not been given, newest first, as
        many as the step's wait for the device leaves room for
        (``REPLAY_US`` each; ``REPLAY_FLOOR`` to ``REPLAY``)."""
        if self._given is None:  # a session's first step
            self._given = self.closed - 1
        room = int(self.ring[-1][SLOT["wait_us"]] / self.REPLAY_US)
        n = min(self.REPLAY, max(self.REPLAY_FLOOR, room))
        first = self.closed - len(self.ring)  # the ring's oldest
        upto = max(first, self._given - n)
        earlier = range(self._given - 1, upto - 1, -1)
        self._given = min(self._given, upto)
        return [as_dict(self.ring[-1])] + [
            as_dict(self.ring[i - first]) for i in earlier]

    def session_over(self) -> None:
        """No session runs: the next one starts over."""
        self._given = None
