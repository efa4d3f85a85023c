"""The input feed on the books: who the loop waited on.

The loop's ``goodput/input_wait`` says that a step waited for data, never on
whom.  Two things say it, both declared here and entered where the work
happens (``data/pipeline.py::DataLoader``,
``parallel/mesh.py::prefetch_to_device``):

* two pairs of counters, always on — plain integer adds, each pair by the
  one thread that consumes, :func:`publish`\\ed into the registry at the log
  cadence the loop already pays and never per step.  Each pair is attempts
  and useful outcomes of one stage: ``input_batch_total`` (a loader handed
  its consumer a batch) over ``input_batch_ready_total`` (that batch was
  built and waiting in the loader's queue when the consumer came: the
  decode kept ahead), and ``input_fetch_total`` (the loop took a placed
  batch off ``prefetch_to_device``) over ``input_fetch_ready_total`` (its
  placement had already finished when it was taken).  A starved loop reads
  a low first share; a slow host-to-device pipe a low second one;
* two spans, entered only while a capture records
  (``trace.annotation(name, batch=i)``; one module attribute read
  otherwise): ``input/batch`` and ``input/place`` (``telemetry/scopes.py``
  says what each covers), each with the batch's index in the epoch as its
  ``batch`` argument, so that one batch's way from the producer to its step
  can be followed by index.

No span sits inside a sample's load: per-sample spans from eight threads are
the overhead this layer must not add.
"""

from __future__ import annotations

from .registry import MetricsRegistry, get_registry

_HELP = {
    "input_batch_total":
        "batches a loader handed to its consumer",
    "input_batch_ready_total":
        "of those, the ones built and queued when the consumer came",
    "input_fetch_total":
        "placed batches the loop took off prefetch_to_device",
    "input_fetch_ready_total":
        "of those, the ones whose placement had finished when taken",
}


class FeedCounts:
    """The four totals since process start.  ``+=`` on an attribute is not
    atomic, so each has ONE writer, the thread that consumes: a loader's
    iterator the first pair, ``prefetch_to_device``'s the second, and in a
    training loop both are the loop.  (Under ``val_overlap`` the validation
    thread consumes its own loader beside the loop and an add may be lost:
    counts for a diagnosis, not a ledger.)"""

    __slots__ = ("batch", "batch_ready", "fetch", "fetch_ready")

    def __init__(self):
        self.batch = self.batch_ready = self.fetch = self.fetch_ready = 0

    def snapshot(self) -> dict:
        return {"input_batch_total": self.batch,
                "input_batch_ready_total": self.batch_ready,
                "input_fetch_total": self.fetch,
                "input_fetch_ready_total": self.fetch_ready}


#: the process's feed (one training loop per process)
COUNTS = FeedCounts()


def publish(registry: MetricsRegistry | None = None) -> dict:
    """Bring the registry's four counters up to the totals; returns the
    totals.  Called at the loop's log cadence and at the end of a fit."""
    reg = registry or get_registry()
    totals = COUNTS.snapshot()
    for name, total in totals.items():
        counter = reg.counter(name, _HELP[name])
        counter.inc(max(0.0, total - counter.value))
    return totals
