"""Rate limiting + regression gating for autopilot actions.

A control loop over a noisy signal flaps without three dampers, and
:class:`ActionGate` is all three in one place:

- **hysteresis** — a trigger must fire ``confirm_n`` consecutive
  observations before it is *confirmed*; one missed observation resets
  the streak. A single slow heartbeat or one bad SLO window never
  moves the fleet.
- **cooldown** — at most one action per ``cooldown_s`` per action
  kind. Remediations act through queues and migrations that take time
  to settle; acting again before the last action's effect is visible
  is how autoscalers oscillate.
- **quarantine** — a trigger whose action was rolled back by the
  regression gate is benched for ``quarantine_base_s``, doubling per
  strike up to ``quarantine_max_s`` (exponential backoff). A trigger
  that keeps producing regressing plans loses the right to re-plan
  until an operator (or :meth:`release`) pardons it.

:func:`verify_measurement` is the regression verdict the apply path
runs after every fleet mutation: a direction-aware tolerance per
metric.
"""
import threading
import time

__all__ = ["ActionGate", "verify_measurement"]


def verify_measurement(before, after, tolerance_pct=10.0,
                       higher_is_better=False):
    """Direction-aware regression verdict on a post-change measurement.

    Returns ``{"regressed": bool, "delta_pct": float|None, ...}``.
    With ``higher_is_better=False`` (step seconds, latency) a rise
    beyond ``tolerance_pct`` regresses; with ``True`` (tokens/sec) a
    fall beyond it does. An unknown side (None / non-positive
    ``before``) yields a non-regressed verdict with ``delta_pct``
    None — the gate can only judge what was measured."""
    try:
        b = None if before is None else float(before)
        a = None if after is None else float(after)
    except (TypeError, ValueError):
        b = a = None
    if b is None or a is None or b <= 0:
        return {"regressed": False, "delta_pct": None,
                "before": before, "after": after,
                "tolerance_pct": float(tolerance_pct)}
    delta_pct = 100.0 * (a - b) / b
    if higher_is_better:
        regressed = delta_pct < -float(tolerance_pct)
    else:
        regressed = delta_pct > float(tolerance_pct)
    return {"regressed": bool(regressed),
            "delta_pct": round(delta_pct, 3), "before": b, "after": a,
            "tolerance_pct": float(tolerance_pct)}


class ActionGate:
    """Hysteresis + per-kind cooldown + per-trigger quarantine.

    ``clock`` is injectable (tests pin time); everything else is
    internally locked — the gate is shared between the loop thread and
    any operator thread poking :meth:`release`."""

    def __init__(self, cooldown_s=5.0, confirm_n=2,
                 quarantine_base_s=30.0, quarantine_max_s=3600.0,
                 clock=time.monotonic):
        self.cooldown_s = float(cooldown_s)
        self.confirm_n = max(1, int(confirm_n))
        self.quarantine_base_s = float(quarantine_base_s)
        self.quarantine_max_s = float(quarantine_max_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._streak = {}       # trigger -> consecutive firing ticks
        self._last_fire = {}    # action kind -> last action stamp
        self._quarantine = {}   # trigger -> {"until": t, "strikes": n}

    # -- hysteresis ------------------------------------------------------
    def confirm(self, trigger, firing):
        """Count one observation of ``trigger``; True once it has fired
        ``confirm_n`` consecutive times. A non-firing observation
        resets the streak (sustained, not cumulative)."""
        with self._lock:
            if not firing:
                self._streak.pop(trigger, None)
                return False
            n = self._streak.get(trigger, 0) + 1
            self._streak[trigger] = n
            return n >= self.confirm_n

    def clear(self, trigger):
        """Reset a trigger's streak (after acting on it: the next
        incident must re-confirm from scratch)."""
        with self._lock:
            self._streak.pop(trigger, None)

    # -- cooldown --------------------------------------------------------
    def ready(self, kind):
        """True when ``kind`` is outside its cooldown window."""
        with self._lock:
            last = self._last_fire.get(kind)
        return last is None or self._clock() - last >= self.cooldown_s

    def stamp(self, kind):
        """Record that an action of ``kind`` just ran."""
        with self._lock:
            self._last_fire[kind] = self._clock()

    # -- quarantine ------------------------------------------------------
    def quarantine(self, trigger):
        """Bench ``trigger`` with exponential backoff; returns the
        backoff seconds granted this strike."""
        with self._lock:
            q = self._quarantine.get(trigger, {"strikes": 0})
            q["strikes"] += 1
            backoff = min(self.quarantine_max_s,
                          self.quarantine_base_s
                          * (2.0 ** (q["strikes"] - 1)))
            q["until"] = self._clock() + backoff
            self._quarantine[trigger] = q
            return backoff

    def quarantined(self, trigger):
        """True while ``trigger`` is benched. Strikes persist past
        expiry — a repeat offender re-enters at double the backoff."""
        with self._lock:
            q = self._quarantine.get(trigger)
            return q is not None and self._clock() < q["until"]

    def release(self, trigger):
        """Operator pardon: lift the bench AND forget the strikes."""
        with self._lock:
            self._quarantine.pop(trigger, None)

    def state(self):
        """Snapshot for journals/tests: streaks, cooldown stamps,
        quarantine table (with remaining seconds)."""
        now = self._clock()
        with self._lock:
            return {
                "streaks": dict(self._streak),
                "cooldowns": {k: round(now - t, 3)
                              for k, t in self._last_fire.items()},
                "quarantine": {
                    t: {"strikes": q["strikes"],
                        "remaining_s": round(max(0.0, q["until"] - now),
                                             3)}
                    for t, q in self._quarantine.items()},
            }
