"""paddle_tpu.observability — unified telemetry hub + flight recorder.

One import point for every instrumented layer::

    from paddle_tpu import observability as obs

    obs.inc("executor.cache_hit")
    obs.observe("checkpoint.save_seconds", dt)
    obs.set_gauge("reader.queue_depth", q.qsize())
    obs.event("retry", source="guard", attempt=2)
    with obs.span("executor.run", program=uid) as sp:
        ...
        sp.note(rows=n)          # fields the block learns on its way
    obs.record_span("decode.queue", t_submit, now, request=rid)

Every helper here is gated on the live ``PADDLE_TPU_TELEMETRY`` mode
(``off`` | ``on`` | ``trace``): with ``off`` each call is a single
env-flag check and an early return — no allocation, no lock — so the
instrumentation stays compiled into the hot paths permanently.

Read side: ``snapshot()`` (nested dict), ``render_prom()`` (Prometheus
text), single-metric probes ``counter(name)`` / ``gauge(name)`` /
``histogram(name)``, ``spans(name=, since=, until=)`` (the span ring),
``get_recorder().dump_jsonl(path)`` (the event ring), and crash dumps
written automatically on uncaught exceptions (see
``recorder.install_excepthook``). ``reset()`` clears the hub AND both
rings — tests use it to scope assertions to a scripted session.

Spans (``observability.tracing``) — one primitive, three sinks. Every
``span`` exit observes ``span.<name>.seconds`` in the hub; appends
``name, t0, t1`` (``time.monotonic()``), thread name, parent span's
name and fields to a process-wide ring of the last 65,536 finished
spans (``spans()`` returns copies; a crash dump carries the tail); and
closes the ``jax.profiler.TraceAnnotation("paddle_tpu.<name>")`` it
opened on entry, so under a running profiler session
(``fluid.profiler`` / ``jax.profiler.start_trace``; open the result in
xprof or Perfetto) the span is written by the profiler on the
profiler's clock, beside the device's "XLA Ops" and "XLA Modules"
lines. ``record_span`` records a span that starts on one thread and
ends on another (ring + histogram). ``off`` records nothing; with no
profiler session an annotation is a flag test. Span names on the two
hot paths:

- ``executor.run`` ⊃ ``executor.feed_convert`` / ``executor.
  device_compute`` (the enqueue) / ``executor.fetch``.
- the decode engine's dispatch thread, per loop iteration, in the
  loop's order (it is pipelined by one step: step n+1 is dispatched
  before step n's tokens are delivered, so the stream threads run while
  the device does): ``decode.loop.admit`` ⊃ ``decode.prefill`` (one
  per request filled; ``decode.adopt`` for a remote handoff) ⊃
  ``decode.prefill.sync``; ``decode.step.dispatch`` (step n+1);
  ``decode.step.emit`` (step n's tokens and ends handed to their
  streams: the first wake-up of a stream thread since the sync);
  ``decode.step.release`` (what is left of step n dropped: device
  buffers are freed with the GIL released, so the thread waits there
  behind the stream threads the emit woke, while the device runs step
  n+1); ``decode.step.sync`` (the wait for step n+1's tokens);
  ``decode.step.decide`` (from those tokens and without touching a
  stream: the next feeds, which slots finish and are freed, what a
  retirement reads of the cache); ``decode.loop.idle`` (one per idle
  stretch). Their exits add to ``DecodeEngine.stats()``:
  ``admit_seconds`` (self time), ``prefill_seconds_total``,
  ``prefill_sync_seconds`` (a part of it), ``dispatch_seconds``,
  ``emit_seconds`` (``emit`` and ``decide``), ``release_seconds``,
  ``sync_seconds``, ``idle_seconds`` — the seven phases sum to the
  thread's wall time; ``loop_cpu_seconds`` beside them is that thread's
  CPU (``time.thread_time()`` once a loop turn: what of the wall time it
  was running) and ``process_cpu_seconds`` every thread's
  (``time.process_time()`` at the call). ``steps_ahead`` in ``stats()``
  (counter ``serving.decode.steps_ahead``) counts the steps dispatched
  while the step before's tokens were still undelivered.
- per request, sharing ``request=<DecodeStream.id>``: ``http.generate``
  (first body byte to terminating chunk; ``status``, ``tokens``,
  ``first_byte_s``, and what the socket cost the stream: ``write_s``,
  the wall time of every chunk's write + flush, over ``chunks``),
  ``decode.queue`` (submit to the start of its prefill),
  ``decode.prefill`` (``slot``, ``bucket``, ``plen``, ``path``),
  ``decode.stream`` (first emit to retire; ``tokens``, ``reason``), and
  ``decode.stream.read``, the READER's side of the stream, recorded by
  ``DecodeStream.tokens()`` on the thread that ran it when the
  generator ends (first ``get`` to the end; sums over the stream:
  ``wake_s``, a ready item waiting for its waiting reader to run, with
  ``wake_max_s`` at ``wake_max_index``; ``consume_s``, what the consumer
  did with each token; ``cpu_s``, the thread's CPU; ``tokens``;
  ``end`` = ``done`` / ``err`` / ``timeout`` / ``closed``). No
  per-token span. A reader that gives up on a stalled stream first
  records a ``stream_stall`` event (source ``serving``: the request,
  its tokens so far, ``active_spans()`` of every thread).

Well-known executor fast-path metrics (PR 4):

- ``compile_cache.disk_hit`` / ``disk_miss`` / ``corrupt`` / ``store``
  / ``store_error`` counters and ``compile_cache.deserialize_seconds``
  / ``serialize_seconds`` histograms — the persistent AOT compile
  cache's disk tier (``fluid.compile_cache``).
- ``executor.overlap_ratio`` gauge — fraction of feed-staging seconds
  that overlapped an in-flight step in the last pipelined run
  (``Executor.run_pipelined``); ``span.executor.stage_feed.seconds`` /
  ``span.reader.stage_feed.seconds`` histograms time the staging
  itself.

Well-known serving metrics (PR 5, ``paddle_tpu.serving``):

- ``serving.queue_wait_seconds`` / ``serving.batch_size`` /
  ``serving.batch_rows`` / ``serving.padding_waste`` /
  ``serving.request_seconds`` histograms — per coalesced micro-batch
  and per request through the ServingEngine.
- ``serving.shed`` / ``serving.deadline_miss`` counters — admission
  control rejects; every reject also records a flight-recorder event
  (kinds ``shed`` / ``deadline_miss``, source ``serving``).
- ``serving.queue_depth.<model>`` gauge, and
  ``predictor.compile_seconds`` histogram with ``compile_start`` /
  ``compile_done`` events (source ``predictor``) — absent entirely on
  a compile-cache warm start.

Well-known serving-fleet metrics (PR 7, ``serving.router``):

- ``serving.replicas_live`` gauge — replicas currently taking traffic;
  ``serving.rollout_state`` gauge — 0 idle / 1 rolling / 2 rolled-back.
- ``serving.failovers`` / ``serving.router_retry`` /
  ``serving.replica_dead`` counters — requests moved to a survivor,
  all-shed backoff rounds, and replicas declared dead (each with a
  flight-recorder event, source ``serving``).
- ``serving.dispatch_seconds`` histogram — router pick-and-submit cost;
  ``elastic.store_scan_cached`` / ``store_scan_full`` counters and the
  ``elastic.store_scan_seconds`` histogram expose the FileStore
  mtime-cache hit rate replica health polling rides on.

Well-known analysis metrics (PR 6, ``paddle_tpu.analysis``):

- ``analysis.verify_seconds`` histogram — cost of the static verify
  gate on each first compile of a signature (executor + predictor);
  ``analysis.findings`` counter — errors+warnings those gates reported.
- ``analysis_report`` events (sources ``executor`` / ``predictor``)
  carry the per-program finding summary; ``analysis_failed`` means the
  analyzer itself crashed (the run proceeds — the gate never blocks on
  its own bugs). ``GuardedExecutor`` retry events gain ``analysis`` /
  ``analysis_findings`` fields from the post-failure full analysis.
- ``scope_race`` events (source ``sanitizer``) — cross-thread Scope
  write violations when ``PADDLE_TPU_SCOPE_SANITIZER=on``.

Well-known cost-model metrics (PR 8, ``analysis.costs`` / ``.memory``):

- ``analysis.predicted_peak_hbm`` gauge — the liveness estimate of the
  peak live-set (bytes) for the last program the executor/predictor
  gate admitted; ``analysis.predicted_mfu`` gauge — the roofline MFU
  prediction (set at ``PADDLE_TPU_ANALYSIS=full``, when the cost pass
  runs). A predicted-OOM program raises before ``compile_start``, so
  these gauges always describe a program that was allowed to compile.
- ``serving.predicted_peak_hbm.<model>`` gauge — worst bucket-ladder
  peak the admission check priced at ``ServingEngine.warmup()``;
  ``bucket_rejected`` events (source ``serving``) record ladders that
  exceeded the HBM budget (the warmup raises before any compile).

Well-known decode-serving metrics (PR 9, ``serving.decode``):

- ``serving.decode.slot_utilization.<engine>`` gauge — live slots /
  total slots after each dispatch iteration (continuous batching keeps
  this near 1.0 under load); ``serving.decode.cache_occupancy.<engine>``
  gauge — filled KV rows / (slots × cache_len).
- ``serving.decode.step_seconds`` histogram — one decode step's
  latency on the host, from the start of its ``decode.step.dispatch``
  to the end of its ``decode.step.sync``, enqueue to tokens on the host;
  the delivery of the step before (``emit``, ``release``) lies between
  the two (also the measured step time the executable ledger's drift
  score reads; until PR 25 it stopped at the enqueue). ``serving.decode.prefill_seconds`` — one slot fill from
  the start of its span to its first token on the host (device wait
  included). ``ttft_seconds`` — submit to first emit inside the
  engine; ``request_seconds`` — submit to retire.
- ``serving.decode.tokens`` / ``requests`` / ``prefills`` / ``steps``
  / ``retired`` / ``shed`` / ``deadline_miss`` / ``cancelled``
  counters — every lifecycle edge ``stats()`` reports, mirrored into
  the hub (``tokens`` once per step with the step's count, not once
  per token); rejects and client disconnects also land in the flight
  recorder with ``engine="decode"``.

Well-known gradient-communication metrics (PR 10, ``parallel/comms``):

- ``comm.bytes_sent`` counter — wire bytes one gradient sync moved
  across the dp group (per step, deterministic from the bucket plan);
  ``comm.bytes_saved`` counter — bytes the quantized path avoided vs
  the fp32 ring over the same padded payload.
- ``comm.compression_ratio`` gauge — fp32 bytes / actual wire bytes of
  the last sync (1.0 on the exact path, ~3.9 at block 256);
  ``comm.overlap_ratio`` gauge — fraction of comm bytes with
  backward-overlap opportunity (0.0 with one bucket or overlap off).
- ``comm.allreduce_seconds`` histogram — the COST-MODEL-predicted comm
  leg per step (wire bytes over the profile's ICI bandwidth,
  ``PADDLE_TPU_ICI_BW`` overridable), not a measurement: inside one
  fused jitted step the per-collective time is not separable host-side.
  Absent when no device profile knows the bandwidth.
- ``collective.dispatch.grad_sync`` counter — each bucketed sync
  dispatch through the FleetGuard collective gate, alongside the
  existing per-op ``collective.dispatch.<op>`` counters.

Well-known disaggregated-serving metrics (PR 12, ``serving.disagg``):

- ``serving.disagg.prefill_live`` / ``decode_live`` gauges — replicas
  of each phase taking traffic; ``serving.disagg.decode_sessions.<rid>``
  gauge — live sessions pinned to each decode replica (the session-
  affinity placement signal).
- ``serving.disagg.sessions`` / ``migrations`` / ``failed_streams`` /
  ``replica_dead`` / ``handoffs`` counters — session lifecycle:
  ``migrations`` counts re-prefill recoveries off dead decode
  replicas, and chaos drills assert ``failed_streams`` stays 0.
- ``serving.disagg.prefill_ttft_seconds`` histogram — queue wait +
  prefill on the prefill fleet (the TTFT SLO leg);
  ``serving.disagg.per_token_seconds`` (and ``.<tenant>``) histograms
  — inter-token gaps on the decode leg (the per-token-p99 SLO leg);
  ``serving.disagg.slo_miss_ttft`` / ``slo_miss_per_token`` counters
  score them against each tenant's targets.
- ``serving.disagg.tenant_live.<tenant>`` gauge and
  ``serving.disagg.tenant_sessions`` / ``tenant_shed`` counters — the
  per-tenant quota accounting behind 429s;
  ``serving.disagg.adopt_seconds`` histogram and
  ``serving.disagg.handoff_bytes.<engine>`` gauge price the KV handoff
  itself (int8 block-scaled wire ≈ 3.9x smaller than fp32).

Well-known KV-reuse + speculation metrics (``serving.spec`` /
``serving.prefix`` / ``serving.tier``):

- ``serving.spec.accept_rate`` (and ``.<engine>``) gauges — cumulative
  accepted draft tokens / proposed, the speculation economics dial
  (tokens-per-dispatch ≈ 1 + k * accept_rate);
  ``serving.spec.round_seconds`` histogram — one draft-propose +
  block-verify round; ``serving.decode.spec_rounds`` /
  ``spec_proposed`` / ``spec_accepted`` / ``spec_fallback_steps`` /
  ``draft_step_errors`` counters (fallbacks are cache-edge demotions
  to the plain step — correctness never depends on the draft).
- ``serving.prefix.hits`` / ``misses`` / ``inserts`` / ``evictions``
  counters and ``serving.prefix.entries`` / ``bytes`` gauges — the
  prefix pool's LRU economy; ``serving.decode.prefix_full_hits`` /
  ``delta_prefills`` counters split hits into zero-dispatch adoptions
  vs suffix-only delta prefills, and
  ``serving.decode.prefill_rows_computed`` / ``prefill_rows_saved``
  counters are the redundant-prefill FLOPs ledger (saved/(saved+
  computed) is the share of prefill rows reuse avoided).
- ``serving.tier.hibernated`` / ``resumed`` / ``evictions`` counters
  and ``serving.tier.sessions`` / ``bytes`` gauges — hibernated
  sessions parked in host RAM (sessions-per-chip = live slots + what
  fits the tier budget); ``serving.decode.hibernated`` / ``resumed``
  count the engine-side lifecycle.

Well-known retrieval metrics (``retrieval.*``, the RetrievalEngine +
ShardedEmbeddingTable from :mod:`paddle_tpu.retrieval`):

- ``retrieval.lookup_seconds`` / ``retrieval.search_seconds``
  histograms — one coalesced dispatch through the ep-sharded gather /
  the chunked brute-force top-k; ``retrieval.batch_rows`` /
  ``retrieval.padding_waste`` histograms — rows per dispatch and the
  pad rows the query-bucket ladder added (a fat waste tail means the
  ladder's rungs don't match the arriving batch sizes).
- ``retrieval.lookups`` / ``retrieval.searches`` / ``retrieval.
  lookup_rows`` / ``retrieval.search_queries`` counters — dispatches
  and per-row/per-query volume (lookup_rows also counts direct
  ``table.lookup()`` calls outside the engine).
- the shared ``serving.queue_depth.<model>`` gauge and
  ``serving.predicted_peak_hbm.<model>`` gauge (worst query-ladder
  rung from ``check_hbm_budget``) carry the same meaning as for the
  other engine kinds, so one dashboard covers all three.

Well-known concurrency/donation metrics (PR 13,
``analysis.concurrency`` / ``analysis.dataflow``):

- ``analysis.lock_graph_edges`` gauge — distinct ``held -> acquiring``
  edges in the armed lock-order graph (``PADDLE_TPU_LOCK_SANITIZER``);
  a growing value means new lock nestings are being exercised.
- ``sanitizer.violations`` counter — every recorded violation across
  BOTH runtime sanitizers: lock-order cycles (``potential-deadlock``),
  ``blocking-under-lock``, ``thread-leak``,
  ``cross-program-donated-alias`` (a zero-copy engine capture of a var
  a training dispatch donates), and scope write races.
- ``threads.leaked`` counter — threads still alive when a component's
  ``stop()``/``close()`` called ``check_stopped`` (counted even
  disarmed; the violation record itself requires the armed sanitizer).
- ``lock_violation`` events (source ``sanitizer``) carry the check
  name, lock names, and thread names of each concurrency violation
  into the flight recorder, next to the existing ``scope_race`` events.

Well-known distributed-tracing + fleet metrics (PR 14,
``observability.distributed``):

- ``trace.spans_exported`` / ``trace.export_errors`` counters — JSONL
  span records appended to ``$PADDLE_TPU_TRACE_DIR`` (one
  ``trace-<pid>.jsonl`` per process; merge them with
  ``python -m paddle_tpu.observability trace <dir>``) and append
  failures. Tracing is opt-in per request via the
  ``TraceContext.sampled`` bit (a ``traceparent`` header or
  ``"trace": true`` in a ``:generate`` body); unsampled requests skip
  every export site.
- ``fleet.replicas`` gauge — replicas merged into the last
  ``/metrics?scope=fleet`` view; ``fleet.<name>`` counter/gauge/
  histogram families — the FleetMetrics merge of per-replica beacon
  docs (counters sum, gauges labeled ``{replica="..."}``, reservoir
  histograms merged), e.g. ``fleet.requests``, ``fleet.tokens``,
  ``fleet.queue_depth{replica="decode-1"}``.
- ``fleet.slo_burn_ttft.<tenant>`` /
  ``fleet.slo_burn_per_token.<tenant>`` gauges — SLOMonitor burn
  rates: (fraction of recent observations over the tenant's
  ``ttft_slo_ms`` / ``per_token_slo_ms`` target) / budget; 1.0 means
  the error budget is being consumed exactly at the allowed rate.
- ``span.*.seconds`` histograms gain distributed siblings: spans
  created with ``ctx=`` (or joined later with ``span.adopt(ctx)``)
  still observe and fill the ring locally but also export, from the
  same exit, trace records whose names carry the phase
  (``serving.http.request``, ``disagg.queue`` / ``.prefill`` /
  ``.handoff`` / ``.adopt``, ``decode.token``), which the collector
  folds into per-phase breakdowns.

Well-known perf-ledger metrics (PR 15, ``observability.ledger`` /
``.perf``):

- ``ledger.registered`` counter — executables recorded in the
  process-wide :class:`ExecutableLedger` (executor step compiles,
  dataset-scan bodies, Predictor engines — serving/decode warmups
  register through the predictor with their own ``kind`` tags — and
  compile-cache disk hits); ``ledger.partial`` counter — entries
  whose executable exposed neither ``cost_analysis()`` nor
  ``memory_analysis()`` (deserialized disk artifacts, backends
  without the API); ``ledger.disk_hits`` counter — entries whose
  source was the compile-cache disk tier.
- ``ledger.entries`` gauge — entries currently held;
  ``ledger.hbm_total_bytes`` gauge — XLA's HBM total (argument +
  output + temp + generated code - aliased) of the last registered
  executable.
- ``ledger.compile_seconds`` histogram — per-registration compile
  cost (absent on disk hits); ``ledger.measured_step_seconds``
  histogram — steady-state step times attached via
  ``note_measured`` (the measured column of the drift table).
- ``executable_registered`` events (source ``ledger``) carry the
  fingerprint prefix, kind, and source of each registration into the
  flight recorder; ``FlightRecorder.crash_dump`` appends the ledger
  tail + compile-cache hit/miss counters so post-mortems show what
  was compiled and resident at death.
- Render the predicted-vs-XLA-vs-measured drift per executable with
  ``python -m paddle_tpu.observability perf <dir|snapshot.json>``
  (an ``ExecutableLedger.snapshot()`` JSON, bare or under a
  ``"ledger"`` key).

Well-known autopilot metrics (PR 16, ``paddle_tpu.autopilot`` — the
self-healing control loop over the ledger/SLO/planner signals above):

- ``autopilot.ticks`` counter — control-loop passes;
  ``autopilot.tick_errors`` — ticks that raised (the daemon loop
  survives and counts them); ``autopilot.actions`` — decisions minted,
  with per-outcome siblings ``autopilot.proposed`` / ``.applied`` /
  ``.verified`` / ``.rolled_back`` / ``.rejected`` /
  ``.quarantined``.
- ``autopilot.calibrations`` counter — DeviceProfile refits from the
  ledger's measured step times; ``autopilot.rollbacks`` counter —
  applied re-plans reverted after a regressing verify measurement;
  ``autopilot.journal_errors`` counter — decision-journal appends that
  could not reach disk (the in-memory ring still holds them).
- ``autopilot.mode`` gauge — 0 off / 1 propose / 2 apply, refreshed
  every tick from ``PADDLE_TPU_AUTOPILOT``;
  ``autopilot.worst_burn`` gauge — the worst per-tenant SLO burn seen
  last tick; ``autopilot.worst_drift_pct`` gauge — the worst
  |measured vs calibrated-predicted| step drift;
  ``autopilot.calibrated_peak_flops`` gauge — the effective peak of
  the latest fit.
- ``autopilot_action`` events (source ``autopilot``) carry each
  decision's kind, trigger, mode, outcome, journal seq, and incident
  trace id into the flight recorder; the same decisions land
  append-only in the ``DecisionJournal`` and as ``autopilot.detect`` /
  ``.replan`` / ``.act`` / ``.apply`` / ``.verify`` spans on the
  request timeline.

Well-known data-integrity metrics (PR 17, ``paddle_tpu.integrity``):

- ``integrity.checkpoint_manifests_written`` counter — per-tensor
  digest manifests written alongside checkpoint saves;
  ``integrity.checkpoint_verified`` — restores whose tensors all
  matched; ``integrity.checkpoint_digest_mismatch`` — tensors that
  did not (restore raises an attributed ``IntegrityError``, consensus
  restore falls back a step); ``integrity.checkpoint_manifest_corrupt``
  — manifests present but unreadable.
  ``integrity.checkpoint_digest_seconds`` / ``checkpoint_verify_seconds``
  histograms price the digest passes (<5% of the save budget).
- ``integrity.handoff_digest_mismatch`` counter — KV handoffs whose
  sealed digest failed on adopt (the stream re-prefills via the
  migration path; ``failed_streams`` stays 0).
- ``integrity.sdc_replay_ok`` / ``sdc_replay_disagree`` counters and
  ``integrity.sdc_replay_seconds`` histogram — the SDC sentinel's
  sampled step replays (1-in-``PADDLE_TPU_SDC_CHECK_EVERY``, default
  128); ``integrity.sdc_vote_confirmed`` / ``sdc_vote_inconclusive``
  — cross-replica vote outcomes; ``integrity.replicas_quarantined``
  — confirmed liars pulled from rotation by the autopilot's
  ``quarantine_replica`` action.
- ``integrity.fault_corrupt_fired`` counter — armed ``corrupt=``
  fault-arm firings; ``compile_cache.corrupt_digest`` /
  ``corrupt_deserialize`` split the existing ``compile_cache.corrupt``
  total by which check caught the entry.
- ``integrity.jsonl_dropped`` counter — torn/unparseable lines skipped
  by the shared tolerant JSONL reader (decision journal, trace
  collector); ``integrity.mailbox_doc_torn`` / ``mailbox_doc_corrupt``
  — FileStore mailbox docs dropped for a torn write vs a failing
  ``_integrity`` stamp.
- ``integrity_violation`` events name the failing check
  (``manifest`` / ``digest`` / ``done-marker`` / ``kv_handoff`` /
  ``mailbox``) and, where known, the tensor — attribution rides the
  event, not just the counter.

Well-known run-health metrics (PR 18, ``observability.runhealth``):

- ``runhealth.steps`` counter — StepSeries records taken;
  ``runhealth.loss`` / ``runhealth.grad_norm`` /
  ``runhealth.loss_scale`` / ``runhealth.step_seconds`` gauges — the
  latest recorded convergence signals.
- ``runhealth.loss_spike`` / ``grad_explosion`` / ``nonfinite_loss``
  / ``plateau`` / ``throughput_sag`` counters — streaming anomaly
  detector firings; each also lands a flight-recorder event (source
  ``runhealth``) carrying the step and the trailing-window evidence.
- ``runhealth.goodput_fraction`` gauge — productive-step seconds /
  run wall-clock at the last ``GoodputAccount.stop()``; the full
  decomposition (``productive_step`` / ``compile`` / ``data_stall``
  / ``checkpoint`` / ``retry_backoff`` / ``restart_rework``) rides
  ``TrainGuard.train()``'s summary and crash dumps (under
  ``"runhealth"``).
- ``amp.loss_scale`` gauge / ``amp.skipped_steps`` counter — the AMP
  decorator's dynamic loss scale and in-graph overflow skips,
  published once per guarded step (``GuardedExecutor`` with
  ``amp_optimizer=``).
- ``autopilot.train_rollbacks`` counter — verified
  ``rollback_lr_cut`` actions the autopilot TRAIN leg executed on
  confirmed divergence; ``autopilot.runhealth_errors`` — detector
  polls that raised.
- Render a run-health report or an A/B comparison with
  ``python -m paddle_tpu.observability run <dir|snapshot.json> [B]``.

Corruption fault grammar (``fluid.resilience``, chaos drills)::

    site:every=N:corrupt=MODE    # or site:at=N:corrupt=MODE

    site  | save    host->disk writes: checkpoint manifests,
          |         compile-cache entries
          | load    disk->host reads of the same artifacts
          | wire    the prefill->decode KV handoff payload
          | mailbox elastic FileStore doc writes
    MODE  | bitflip flip one bit mid-payload (silent corruption)
          | truncate keep the first half (short read/write)
          | torn    drop the tail (interrupted append)

``corrupt=`` arms only those four byte-path sites; parse rejects any
other site, a missing mode, or an unknown mode. All other sites keep
their existing arms (``exception`` / ``slow=SECONDS`` / ``hang`` ...).

This package is stdlib-only (no jax/numpy imports at module level), so
crash-path and supervisor code can use it without accelerator init.
"""
from . import distributed as _distributed
from . import ledger as _ledger_mod
from . import perf as _perf_mod
from . import recorder as _recorder
from . import telemetry as _telemetry
from . import tracing as _tracing
from .distributed import (  # noqa: F401
    TRACE_DIR_ENV, TRACE_PROC_ENV, TRACE_SAMPLE_ENV, FleetMetrics,
    SLOMonitor, TraceContext, chrome_trace, collect_trace, export_span,
    phase_breakdown, process_label, read_spans, replica_metrics_doc,
    sample_request, set_process_label, trace_dir,
)
from .ledger import ExecutableLedger, get_ledger  # noqa: F401
from .perf import (  # noqa: F401
    drift_rows, drift_summary, load_snapshot, render_drift_table,
)
from . import runhealth as _runhealth_mod
from .runhealth import (  # noqa: F401
    GoodputAccount, RunHealth, StepSeries, load_run,
    render_comparison, render_health_report,
)
from .recorder import (  # noqa: F401
    CRASH_DUMP_ENV, FlightRecorder, crash_dump_path, get_recorder,
    install_excepthook,
)
from .telemetry import (  # noqa: F401
    OFF, ON, TRACE, TELEMETRY_ENV, PROM_STYLE_ENV, Histogram,
    Telemetry, get_telemetry, mode,
)
from .tracing import (  # noqa: F401
    active_spans, current_span, record_span, span, spans,
)

__all__ = [
    "Telemetry", "Histogram", "FlightRecorder", "get_telemetry",
    "get_recorder", "span", "spans", "record_span", "active_spans",
    "current_span", "mode",
    "enabled", "trace_enabled", "inc", "observe", "set_gauge", "event",
    "counter", "gauge", "histogram",
    "snapshot", "render_prom", "reset", "install_excepthook",
    "crash_dump_path", "TELEMETRY_ENV", "CRASH_DUMP_ENV",
    "OFF", "ON", "TRACE",
    "TraceContext", "TRACE_DIR_ENV", "TRACE_PROC_ENV",
    "TRACE_SAMPLE_ENV", "trace_dir", "sample_request",
    "process_label", "set_process_label", "export_span", "read_spans",
    "chrome_trace", "collect_trace", "phase_breakdown", "FleetMetrics",
    "SLOMonitor", "replica_metrics_doc", "PROM_STYLE_ENV",
    "ExecutableLedger", "get_ledger", "drift_rows", "drift_summary",
    "load_snapshot", "render_drift_table",
    "StepSeries", "GoodputAccount", "RunHealth", "load_run",
    "render_health_report", "render_comparison",
]


def enabled():
    """True unless PADDLE_TPU_TELEMETRY=off."""
    return _telemetry.mode() != OFF


def trace_enabled():
    """True only in PADDLE_TPU_TELEMETRY=trace mode."""
    return _telemetry.mode() == TRACE


# -- mode-gated write helpers (the instrumentation surface) ----------------

def inc(name, n=1):
    if _telemetry.mode() == OFF:
        return
    _telemetry._hub.inc(name, n)


def observe(name, value):
    if _telemetry.mode() == OFF:
        return
    _telemetry._hub.observe(name, value)


def set_gauge(name, value):
    if _telemetry.mode() == OFF:
        return
    _telemetry._hub.set_gauge(name, value)


def event(kind, source=None, recorder=None, count=True, **fields):
    """Record a structured event into `recorder` (the global flight
    recorder when None) and bump the ``<source>.<kind>`` counter. The
    single entry point EventLog streams route through."""
    if _telemetry.mode() == OFF:
        return None
    if count:
        _telemetry._hub.inc(
            "%s.%s" % (source, kind) if source else kind)
    rec = recorder if recorder is not None else _recorder._global
    if source is not None:
        fields.setdefault("source", source)
    return rec.record(kind, **fields)


# -- read side --------------------------------------------------------------

def counter(name):
    """Current value of one counter (0 when never bumped) — the cheap
    single-metric probe tests and bench reporting use instead of a full
    snapshot()."""
    return _telemetry._hub.counter(name)


def gauge(name):
    """Current value of one gauge, or None when never set."""
    return _telemetry._hub.gauge(name)


def histogram(name):
    """Summary dict of one histogram, or None when never observed."""
    return _telemetry._hub.histogram(name)


def snapshot():
    return _telemetry._hub.snapshot()


def render_prom(style=None):
    return _telemetry._hub.render_prom(style=style)


def reset():
    """Clear the hub, the global event ring, the span ring, the
    executable ledger, and the active run-health bundle (testing /
    session scoping). Does not uninstall the excepthook."""
    _telemetry._hub.reset()
    _recorder._global.clear()
    _tracing.clear_spans()
    _ledger_mod._global.clear()
    _runhealth_mod.reset()
