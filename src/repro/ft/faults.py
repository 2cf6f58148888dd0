"""Failure taxonomy + per-class recovery policies.

Every ingest boundary and the step supervisor route failures through ONE
classification so "what went wrong" and "what to do about it" are decided
in one place instead of per ``except`` clause:

=================  =====================================================
class              policy (``policy_for``)
=================  =====================================================
``CorruptStream``  ``recompute-dense`` — the (bitmap, payload) stream
                   failed the wire contract (``compress.integrity``);
                   re-request / recompute the map from its dense source
                   (serve replaces the leaf with the dense cache, the
                   engine and collectives re-run the dense path, restore
                   walks back the checkpoint chain).
``TransientStep``  ``restore-retry`` — a step failed for a reason that a
                   restore + retry plausibly clears (preempted device,
                   transient XLA error). The supervisor restores the
                   newest verified checkpoint with exponential backoff.
``PoisonBatch``    ``skip-batch`` — the *data* is bad (non-finite loss /
                   gradients from one batch); restoring would replay the
                   same batch into the same failure. Log it, skip it,
                   keep the state.
``DeviceLoss``     ``remesh`` — the device topology changed; the state
                   must be re-sharded over the live devices
                   (``ft.supervisor.remesh_state``) before stepping.
``DeadlineExceeded``  ``shed`` — a request blew its SLO (TTL in engine
                   ticks). The scheduler drops it with
                   ``status="shed"``; nothing about the *system* is
                   wrong, so it is logged but NOT counted against
                   ``max_failures``.
``Overload``       ``shed`` — the bounded pending queue overflowed.
                   Same accounting as ``DeadlineExceeded``: load
                   shedding is the system working as designed, not a
                   failure budget event.
=================  =====================================================

Everything else — ``KeyboardInterrupt``, ``SystemExit``, assertion and
programming errors — is *not* a fault: :func:`classify` returns ``None``
and the supervisor re-raises. The old behavior (every ``Exception`` is
retryable) turned typos into max_failures restore loops.
"""
from __future__ import annotations


class FaultError(RuntimeError):
    """Base of the classified failure taxonomy."""


class CorruptStream(FaultError):
    """A (bitmap, payload) stream failed the wire contract on ingest."""


class TransientStep(FaultError):
    """A step failure that restore + retry plausibly clears."""


class PoisonBatch(FaultError):
    """One batch produced non-finite loss/grads — skip it, keep state."""


class DeviceLoss(FaultError):
    """The device topology changed under the job."""


class DeadlineExceeded(FaultError):
    """A request blew its deadline (TTL in engine ticks) — shed it."""


class Overload(FaultError):
    """The bounded pending queue overflowed — shed the newest arrivals."""


POLICIES: dict[type, str] = {
    CorruptStream: "recompute-dense",
    TransientStep: "restore-retry",
    PoisonBatch: "skip-batch",
    DeviceLoss: "remesh",
    DeadlineExceeded: "shed",
    Overload: "shed",
}

# policies that are normal-operation outcomes, not system failures:
# the supervisor logs them but never counts them toward max_failures
SHED_POLICIES = ("shed",)

# Exception text markers that identify a known transient-infrastructure
# failure when the raiser didn't use the taxonomy (e.g. JAX's
# JaxRuntimeError). Deliberately narrow: an unrecognized error is a bug
# and must surface, not retry. RESOURCE_EXHAUSTED (HBM/VMEM/SMEM
# overflow) and INTERNAL (e.g. "Mosaic failed to compile") are left out:
# both are deterministic for a given program and shape, so a retry would
# only fail again.
_TRANSIENT_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                      "preempt", "socket closed", "connection reset")
_POISON_MARKERS = ("nan", "non-finite", "not finite", "inf loss")


def classify(exc: BaseException) -> type[FaultError] | None:
    """Map an exception onto its fault class, or ``None`` for
    "not a fault — re-raise". Explicit taxonomy instances win; known
    infrastructure errors match by status marker; anything else
    (including ``KeyboardInterrupt``/``SystemExit``, which are not even
    ``Exception``s) is unclassified."""
    if isinstance(exc, FaultError):
        for cls in (CorruptStream, TransientStep, PoisonBatch, DeviceLoss,
                    DeadlineExceeded, Overload):
            if isinstance(exc, cls):
                return cls
        return TransientStep
    if not isinstance(exc, Exception):
        return None                      # KeyboardInterrupt / SystemExit
    msg = f"{type(exc).__name__}: {exc}"
    low = msg.lower()
    if type(exc).__name__ == "JaxRuntimeError":     # jax.errors, by name:
                                                    # this module imports no jax
        if any(m.lower() in low for m in _TRANSIENT_MARKERS):
            return TransientStep
    if isinstance(exc, FloatingPointError) or \
            any(m in low for m in _POISON_MARKERS):
        return PoisonBatch
    if isinstance(exc, (RuntimeError, OSError, ConnectionError)) and \
            any(m.lower() in low for m in _TRANSIENT_MARKERS):
        return TransientStep
    return None


def policy_for(exc: BaseException) -> str | None:
    """The recovery policy name for an exception, or ``None`` (re-raise)."""
    cls = classify(exc)
    return POLICIES[cls] if cls is not None else None
