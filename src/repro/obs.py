"""Trace spans of the log, the ingest front end and the trainer.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``.  With no
profiler session running it records nothing and costs under a
microsecond; inside a session (``jax.profiler.start_trace``) it lands
on the host planes of the session's ``.xplane.pb``, on the same clock
as the device's ops, one line per thread.  The session is the only
switch.

Every name the program emits is one of the constants below: the spans
(``NAMES``) and the counters (``COUNTERS``).  The only
metadata is ``round=<end LSN>`` on a durability round's issue, lane
write and retire spans, so that one round can be followed across the
threads that handle it.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

# ingest front end (core/ingest.py)
INGEST_ADMIT = "arcadia.ingest.admit"     # producer blocked for queue space
INGEST_WAVE = "arcadia.ingest.wave"       # one wave on the collector:
                                          # reserve, copy, complete, forces
INGEST_ACK = "arcadia.ingest.ack"         # resolving the tickets a durable
                                          # watermark advance covers

# host append path (core/log.py)
LOG_RESERVE = "arcadia.log.reserve"       # ring space and LSNs
LOG_COPY = "arcadia.log.copy"             # payload bytes into the ring
LOG_COMPLETE = "arcadia.log.complete"     # checksums, headers, watermark
LOG_HASH = "arcadia.log.hash"             # one record's lane row and hash

# integrity kernel, host side (kernels/checksum/ops.py)
CHECKSUM_CALL = "arcadia.checksum.call"   # host-to-device copy, kernel and
                                          # read-back (NumPy off the TPU)

# force and replication (core/log.py, primitives.py, transport.py)
LOG_ISSUE = "arcadia.log.issue"           # force leader: slot wait, post,
                                          # local flush (round=end LSN)
REPL_POST = "arcadia.repl.post"           # one backup's snapshot of the range
LOG_FLUSH = "arcadia.log.flush"           # local persist of the range
REPL_LANE = "arcadia.repl.lane"           # remote write and persist on one
                                          # backup's lane (round=end LSN)
LOG_RETIRE = "arcadia.log.retire"         # watermark advance (round=end LSN)

# recovery (core/log.py: Log.open)
OPEN = "arcadia.open"                     # the whole recovery
OPEN_SNAPSHOT = "arcadia.open.snapshot"   # one read of the ring
OPEN_PLAN = "arcadia.open.plan"           # chain walk and vectorized plan
OPEN_VALIDATE = "arcadia.open.validate"   # payload checks of the chain
OPEN_LANES = "arcadia.open.lanes"         # lane-matrix build for the hash

# trainer host loop (train/trainer.py)
TRAIN_BATCH = "arcadia.train.batch"       # batch_at and the copy to device
TRAIN_STEP = "arcadia.train.step"         # dispatch of the jitted step
TRAIN_LOSS = "arcadia.train.loss"         # waiting for the step's loss
TRAIN_JOURNAL = "arcadia.train.journal"   # the step's journal record

# checkpoint (checkpoint/manager.py)
CKPT_SNAPSHOT = "arcadia.ckpt.snapshot"   # on the caller: start the state's
                                          # device-to-host copies (NumPy
                                          # leaves: the copy itself)
CKPT_WRITE = "arcadia.ckpt.write"         # fetch, encode, store puts,
                                          # manifest
CKPT_FETCH = "arcadia.ckpt.fetch"         # waiting out the state's copies
                                          # to the host, inside the write

NAMES = frozenset(v for k, v in list(globals().items())
                  if k.isupper() and isinstance(v, str)
                  and v.startswith("arcadia."))

# counters: running totals a reader takes the difference of over a window
COLLECTED = "collected"                   # IngestEngine.stats(): records
QUEUE_WAIT_S = "queue_wait_s"             # taken by the collector; their
                                          # summed wait in the queue
ROUNDS_RETIRED = "rounds_retired"         # Log.stats(): durability rounds
ROUND_WALL_S = "round_wall_s"             # retired; their issue-to-retire
                                          # seconds
CKPTS_SAVED = "ckpts_saved"               # TrainerReport: checkpoints
CKPTS_SKIPPED = "ckpts_skipped"           # dispatched; skipped because
                                          # the previous one still wrote
MOE_ROWS = "moe_rows"                     # TrainerReport and the step's
MOE_MAX_ROWS = "moe_max_rows"             # metrics: rows the held experts
                                          # computed, and the largest held
                                          # expert's rows, summed over the
                                          # steps and the expert layers

COUNTERS = frozenset((COLLECTED, QUEUE_WAIT_S, ROUNDS_RETIRED, ROUND_WALL_S,
                      CKPTS_SAVED, CKPTS_SKIPPED, MOE_ROWS, MOE_MAX_ROWS))


def span(name: str, **meta) -> TraceAnnotation:
    """A trace span named ``name`` (one of ``NAMES``)."""
    return TraceAnnotation(name, **meta)
