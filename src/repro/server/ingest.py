"""Summary-ingest queue (DESIGN.md §8).

Client summary recomputation finishes *somewhere else* — on the device, a
network round-trip away.  The async server models that with an explicit
queue: a batch computed at round ``r`` becomes ready at round
``r + delay`` and only then scatters into the live registry (the same
O(M) ``RoundContext.ingest`` row scatter the sync loop uses).

Two invariants matter for the pipeline:

  * **in-flight dedup** — a client whose refresh is already queued must
    not be re-issued by the next round's drift scan (its registry row
    still looks stale until the batch lands); ``in_flight`` feeds the
    scan's exclusion set.
  * **FIFO drain** — batches land in compute order, so a client refreshed
    twice while latency accrues converges to its *newest* summary (later
    batches overwrite earlier rows at drain time).

With ``delay == 0`` the queue is transparent: batches drain in the same
round they were computed, before clustering and selection — the
degenerate setting the async ≡ sync differential pins.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import repro.obs as obs


class IngestOverflow(RuntimeError):
    """A bounded ingest queue was asked to accept more in-flight summaries
    than ``max_depth``.  The admission controller (``server/admission.py``)
    is the component that prevents this by shedding load *before* the
    enqueue; hitting it means a caller bypassed admission control."""


@dataclasses.dataclass(frozen=True)
class SummaryBatch:
    """One round's recomputed summaries, in ingest (registry write) order."""
    compute_round: int                    # the data's age (last_refresh)
    ready_round: int                      # when the batch may land
    summaries: dict                       # {client: summary np.ndarray}
    fresh_rows: dict                      # {client: cheap P(y) row}
    retries: int = 0                      # redeliveries after injected loss

    def __len__(self) -> int:
        return len(self.summaries)


class IngestQueue:
    """FIFO of in-flight summary batches, drained by readiness round.

    ``max_depth`` bounds the total number of in-flight *summaries* (rows,
    not batches); 0 means unbounded — the historical behavior, and a
    latent memory bug at 1M clients, which is why the bounded front end
    always sets it.  Overflow raises ``IngestOverflow`` loudly instead of
    silently growing: backpressure decisions belong to the admission
    controller, not to the queue.
    """

    def __init__(self, max_depth: int = 0):
        if max_depth < 0:
            raise ValueError("max_depth must be >= 0 (0 = unbounded)")
        self.max_depth = int(max_depth)
        self._pending: list[SummaryBatch] = []
        self._depth = 0                       # in-flight summaries (rows)
        self.enqueued_batches = 0
        self.drained_batches = 0
        self.requeued_batches = 0

    def __len__(self) -> int:
        return len(self._pending)

    def depth(self) -> int:
        """In-flight summaries (rows) across all queued batches."""
        return self._depth

    def capacity(self) -> int:
        """Rows that may still be enqueued before overflow (a very large
        number when unbounded) — the admission controller's budget."""
        if self.max_depth <= 0:
            return 1 << 62
        return max(self.max_depth - self._depth, 0)

    def enqueue(self, compute_round: int, delay_rounds: int,
                summaries: dict, fresh,
                ready_round: int | None = None) -> SummaryBatch | None:
        """Queue one compute round's results; ``fresh`` is indexable by
        client id (the round's [N, C] cheap-signal array, or a per-id
        dict for re-admitted deferred summaries).  ``ready_round``
        overrides the default ``compute_round + delay_rounds`` readiness
        (deferred batches land relative to their *admission* round, not
        the round their data was computed).  Returns the batch, or None
        when there is nothing to send."""
        if not summaries:
            return None
        if self.max_depth > 0 and self._depth + len(summaries) > \
                self.max_depth:
            raise IngestOverflow(
                f"ingest queue overflow: {self._depth} summaries in "
                f"flight + {len(summaries)} offered > max_depth="
                f"{self.max_depth} (admission control should have shed "
                f"this batch)")
        batch = SummaryBatch(
            compute_round=int(compute_round),
            ready_round=(int(compute_round) + int(delay_rounds)
                         if ready_round is None else int(ready_round)),
            summaries=dict(summaries),
            fresh_rows={c: np.asarray(fresh[c]) for c in summaries})
        self._pending.append(batch)
        self._depth += len(batch)
        self.enqueued_batches += 1
        obs.instant("ingest/enqueue", cat="ingest", batch=len(batch),
                    compute_round=batch.compute_round,
                    ready_round=batch.ready_round)
        m = obs.metrics()
        m.counter("server/ingest/enqueued_batches").inc()
        m.counter("server/ingest/enqueued_summaries").inc(len(batch))
        return batch

    def pop_ready(self, round_idx: int) -> list[SummaryBatch]:
        """All batches whose latency has elapsed, in enqueue (FIFO) order."""
        ready = [b for b in self._pending if b.ready_round <= round_idx]
        if ready:
            self._pending = [b for b in self._pending
                             if b.ready_round > round_idx]
            self._depth -= sum(len(b) for b in ready)
            self.drained_batches += len(ready)
            obs.instant("ingest/drain", cat="ingest", round=round_idx,
                        batches=len(ready),
                        in_flight=len(self._pending))
            m = obs.metrics()
            m.counter("server/ingest/drained_batches").inc(len(ready))
            for b in ready:
                m.histogram("server/ingest/latency_rounds",
                            lo=0.5, hi=1e4, per_decade=16) \
                    .record(round_idx - b.compute_round)
        return ready

    def requeue(self, batch: SummaryBatch, ready_round: int) -> SummaryBatch:
        """Redeliver a lost batch (fault injection): same payload, one
        more retry, ready after the backoff.  Appended at the tail — a
        redelivery is a *later* arrival, so FIFO convergence to the
        newest summary still holds."""
        redo = dataclasses.replace(batch, ready_round=int(ready_round),
                                   retries=batch.retries + 1)
        self._pending.append(redo)
        self._depth += len(redo)
        self.requeued_batches += 1
        obs.instant("ingest/requeue", cat="ingest", batch=len(redo),
                    retries=redo.retries, ready_round=redo.ready_round)
        obs.metrics().counter("server/ingest/requeued_batches").inc()
        return redo

    def in_flight(self) -> set:
        """Client ids with a queued-but-not-landed refresh (scan dedup)."""
        ids: set = set()
        for b in self._pending:
            ids.update(b.summaries)
        return ids

    def pending(self) -> list[SummaryBatch]:
        """In-flight batches in FIFO order (checkpointing)."""
        return list(self._pending)

    def load(self, batches: list[SummaryBatch], enqueued: int, drained: int,
             requeued: int = 0) -> None:
        """Restore a checkpointed queue (batches in FIFO order)."""
        self._pending = list(batches)
        self._depth = sum(len(b) for b in self._pending)
        self.enqueued_batches = int(enqueued)
        self.drained_batches = int(drained)
        self.requeued_batches = int(requeued)
