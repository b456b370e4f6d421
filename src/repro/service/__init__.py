"""Online scheduler service: the event core as a live decision engine.

``Dispatcher`` holds the event-granular scan's carry (node-free / power
tables, pending buffer, reservations) as long-lived state: jobs are
submitted one at a time, the clock is driven through bounded horizons,
and every step emits the placement decision the batch scan would have
made — bit-identically (tests/test_service.py).  ``SessionPool`` scales
that out: N sessions as one stacked carry advanced by a single jitted
vmapped step, with batched intake and an async writer for decision
records and checkpoints (tests/test_service_pool.py).  ``whatif`` forks
the live carry into a jitted rollout for operator queries;
``ServiceMetrics`` streams queue / power / latency counters;
``repro.launch.scheduler_service`` is the JSONL CLI loop (single
session or ``--pool N``).  See docs/SERVICE.md.
"""

from repro.service.dispatcher import Dispatcher, own_columns
from repro.service.metrics import ServiceMetrics
from repro.service.pool import SessionPool
from repro.service.whatif import whatif
from repro.service.writer import AsyncWriter

__all__ = ["AsyncWriter", "Dispatcher", "ServiceMetrics", "SessionPool",
           "own_columns", "whatif"]
