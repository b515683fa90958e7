"""Registered dotted-name taxonomy for spans, counters, and events.

The profiler report, the Chrome-trace exporter, the engine-metrics
autologger, and the benchmark's per-layer readers all key off these
names; a call site inventing `staging.h2dBytes` next to
`staging.h2d_bytes` silently splits a metric in two. Every
`PROFILER.span`/`PROFILER.count` and `RECORDER.emit/counter/gauge` call
site is AST-linted against this registry (graftlint rule `obs-taxonomy`
in sml_tpu/lint/rules/taxonomy.py, enforced by tests/test_obs_taxonomy.py
and tests/test_lint_clean.py), and every entry here has a call site that
can emit it (the same rule's `unemitted_patterns`).

Entries are exact names or `prefix.*` wildcards (wildcards cover the
f-string sites whose suffix is runtime data: the op behind a
`materialize.<op>` span, the fn behind `program.<name>`).
"""

from __future__ import annotations

from typing import Iterable

SPANS = {
    # frame engine
    "materialize.*",
    "shuffle.partition", "shuffle.dropDuplicates", "shuffle.join",
    "shuffle.sort", "shuffle.repartition",
    # ML engine
    "fused_transform", "binning.predict",
    "program.*",          # program.<fn> / program.tree_ensemble / ...
    # one span tree a fit (docs/OBSERVABILITY.md "The span tree of a
    # fit"): the root `fit` opened by the outermost Estimator.fit, and
    # its host phases fit.collect / fit.prep / fit.featurize /
    # fit.quantize (.key, .bins with its phases .stats and .digitize) /
    # fit.stage / fit.baseline, plus
    # fit.dispatch / fit.device_wait / fit.readback / fit.unpack inside
    # the tree programs' spans and, but for fit.unpack, inside every
    # `_staging.run_data_parallel` program (the linear family's), and
    # fit.summary (the logistic training summary's host pass); inside
    # the `fit.featurize` spans their children fit.featurize.plan.jobs /
    # .plan.block (the column plan), .extract (a tree fit's label column
    # and its test for a label that is not finite; the block is gathered
    # only where one is: a fit's `missing` is a compare of the quantizer's
    # jobs and opens no span), .als.index and .als.sort (a factorization's
    # dense ids, and its two sorted orders with their bounds)
    "fit", "fit.*",
    # ALSModel.transform's look-up of a partition's users and movies and
    # their factors' dot products (ml/recommendation.py)
    "transform.als.lookup",
    # what of a clustering's seeding runs on the host (ml/clustering.py
    # KMeans._fit: the draws' key, the rows of initMode="random"; the
    # rounds of k-means|| are inside the fit's ONE dispatch)
    "kmeans.init.local",
    # the staging functions' steps for an array of at least 1 MiB
    # (ml/_staging.py `_SPAN_BYTES`; shared with scoring and serving, so
    # named for the function): stage.key (normalize + content key + cache
    # look-up) / stage.pad (the padded host copy) / stage.put (device_put
    # until it returns); inside `fit.stage` during a fit
    "stage.*",
    # serving layer: one coalesced device dispatch of the micro-batcher
    "serve.batch",
    # the interpreter's collector (obs/_recorder.py `_GcPauses`, a
    # `gc.callbacks` hook while the recorder is on): gc.pause, one span a
    # collection of 1 ms or more or of generation 2 (`generation`,
    # `collected`), on the thread it ran on; shorter ones are in the totals
    # gc.pause_s / gc.collections alone
    "gc.*",
    # per-device straggler attribution (obs/_skew.py): skew.compute /
    # skew.wait lanes rendered on the trace exporter's per-device process
    "skew.*",
    # chunked-ingest per-CHUNK attribution lanes (the INGEST_SKEW
    # tracker): ingest.compute / ingest.wait with "device" = chunk index
    "ingest.*",
}

#: the host phases of a fit, by the benchmark metric that reports each: the
#: same name lists as `benchmark/layer_metrics/_fit_spans.PHASES` (the
#: program cannot import the benchmark; `tests/test_fit_records.py` holds
#: the two equal). A fit's record (`obs/_fits.py`) makes its `phases` from
#: them; with `fit.host.unattributed_s` they sum to the root span `fit`
FIT_PHASES = {
    "fit.host.featurize_s": ("fit.collect", "fit.prep", "fit.featurize"),
    "fit.host.quantize_s": ("fit.quantize",),
    "fit.host.stage_s": ("fit.stage",),
    "fit.host.dispatch_s": ("fit.dispatch",),
    "fit.host.device_wait_s": ("fit.device_wait",),
    "fit.host.readback_s": ("fit.readback", "fit.unpack"),
    "fit.host.observe_s": ("fit.baseline",),
}

#: spans that read the PROCESS's CPU seconds at their two ends while the
#: recorder is on (`Profiler.span`): the root, the spans the phases are made
#: of and the three parts of `unattributed_s` that have spans; not their
#: children, not `stage.*`. Wall seconds cannot say why a phase was slow:
#: 5 s of wall and 0.05 s of CPU was not running, 5 s of both was working.
#: Process-wide on purpose: a pooled phase's work is on the pool's threads,
#: so `span_cpu_s.<name>` over `span_s.<name>` is the cores it kept busy,
#: and `fit.device_wait`'s is what the runtime's threads burn while the
#: host thread sleeps. A read is a system call (6 us on the chip's host,
#: about 30 a fit) and the clock ticks in 10 ms steps there: nothing for a
#: span of a few milliseconds, plenty for a stall
CPU_SPANS = frozenset(
    {"fit", "fit.summary", "fit.cv.folds", "fit.cv.eval"}
    | {name for names in FIT_PHASES.values() for name in names})

COUNTERS = {
    # running totals the recorder keeps for EVERY span name (no call
    # site): span_s.<name> seconds inside spans of that name,
    # span_n.<name> how many ended — read as deltas between two
    # `RECORDER.counters()` snapshots
    "span_s.*", "span_n.*",
    # and for the spans of `CPU_SPANS` alone: CPU seconds (user + system,
    # every thread) of the process between the span's two ends
    "span_cpu_s.*",
    # the collector's pauses, every collection of every generation (no ring
    # event: generation 0 runs hundreds of times a fit): gc.pause_s seconds
    # between a collection's two callbacks / gc.collections how many
    "gc.*",
    # a fit's record (obs/_fits.py), added when the root `fit` closes with
    # no ring event: fit.gc_s the collector's pauses inside root fits (the
    # untimed splits' are in gc.pause_s and not here) / fit.slow fits whose
    # wall passed their shape's median by a quarter and by 0.1 s /
    # fit.slow.excess_s their seconds over that median
    "fit.gc_s", "fit.slow", "fit.slow.excess_s",
    # the watchdog's own lateness (obs/_watchdog.py `_loop`): seconds its
    # `wait` returned later than asked, summed beyond 50 ms a wait: the
    # whole process, or the machine, stood still
    "watchdog.late_s",
    # stall watchdog (obs/_watchdog.py): flagged in-flight tickets
    "stall.*",
    # black-box postmortem (obs/blackbox.py): bundles written
    "blackbox.*",
    # out-of-core data plane (frame/_chunks.py + ml/_chunked.py):
    # ingest.chunks / ingest.rows / ingest.raw_bytes (float bytes the
    # chunk plane SAW but never held whole) / ingest.h2d_bytes (compact
    # chunk-block transfers) / ingest.sketch_compress / ingest.memo_hit
    "ingest.*",
    "staging.cache_hit", "staging.cache_miss",
    "staging.bin_cache_hit", "staging.bin_cache_miss",
    "staging.h2d_bytes", "staging.d2h_bytes", "staging.h2d_bytes_saved",
    "staging.evict_bytes", "staging.bin_evict_bytes",
    # pad steps over `_SPAN_BYTES` (ml/_staging.py `_zero_tailed`): written
    # into a retained buffer whose pages are warm / into a fresh allocation
    # (the first of a size, and every one where a placed array may alias
    # the host: the CPU backend)
    "staging.pad_warm", "staging.pad_fresh",
    "shuffle.rows", "shuffle.bytes",
    "cv.batchFolds.fallback",
    # a validator's fit (ml/tuning.py): estimator fits it made (the grid's
    # over the folds and the refit) / validation metrics it took / fold
    # frames it made (splits and unions; 0 where the folds are a mask over
    # one staged block)
    "cv.fits", "cv.evals", "cv.fold_frames",
    # a factorization's fit (ml/recommendation.py ALS._fit): fits /
    # half-steps the ONE dispatch ran, read back with the factors (2 x
    # maxIter) / blocks of rows a half-step walks a shard's sorted order
    # in (`_block_rows`) / training ratings; and rows ALSModel.transform
    # dropped under coldStartStrategy="drop". On the device the
    # `jax.named_scope`s als.gather / als.normal (inside it
    # als.normal.tiles: a block's masked product a tile;
    # als.normal.carry: the levels above and the entities' gathers;
    # als.normal.allreduce) / als.solve
    "als.fits", "als.half_steps", "als.blocks", "als.ratings",
    "als.cold_start.dropped",
    # a clustering's fit (ml/clustering.py KMeans._fit), every count
    # read back with the centers of the ONE dispatch: fits / Lloyd steps
    # run / fits that `tol` ended (not `maxIter`) / rounds of k-means||,
    # each over all rows / candidates the rounds kept (the first center
    # among them) / blocks of rows a step walks a shard in
    # (`_block_rows`) / rows assigned, summed over the steps (rows x
    # iterations) / clusters the last step left empty (each kept its
    # center). On the device the `jax.named_scope`s kmeans.init (the
    # seeding's passes, the candidates' weights and the weighted
    # k-means++) / kmeans.assign (a block's distance product and arg-min)
    # / kmeans.update (the 0/1 product, the all-reduce, the new centers)
    # / kmeans.cost (the pass at the returned centers)
    "kmeans.fits", "kmeans.iterations", "kmeans.converged",
    "kmeans.init.rounds", "kmeans.init.candidates", "kmeans.blocks",
    "kmeans.rows", "kmeans.empty_clusters",
    # Pallas launches of the traversal kernel (native/traverse_kernel.py,
    # docs/KERNELS.md): TRACE-TIME statics (counted once per program
    # trace, like collective.*: launches per execution = the count ×
    # executions); kernel.interpret counts those traced in interpret mode
    "kernel.pallas_launch", "kernel.interpret",
    # host-side C++ libraries (native/build.py): a library that could not
    # be built or loaded, so its callers run the NumPy implementation
    "native.build_failed",
    # fused traversal kernel on the SCORING path (native/traverse_kernel
    # + ml/inference.py resolution): infer.kernel.pallas / infer.kernel.xla
    # count spec resolutions landing on each path; infer.kernel.fallback
    # counts dispatches that `auto` wanted on pallas but that demoted
    # to XLA
    "infer.kernel.*",
    "compile.programs",
    "compile.program.*",  # per-name program-cache-miss counts
    "tree.fit_dispatch",  # device launches of tree-fit programs (the
                          # grid-fused CV dispatch-count contract)
    # the histogram operand a tree-fit dispatch builds
    # (tree_impl._tree_operand, counted by _count_operand beside
    # tree.fit_dispatch): the one-hot's elements over all devices at numpy's
    # itemsize of the stored type (a byte for the chip's int4, which holds
    # half of it: _count_operand says why) / the row blocks one device's
    # loop walks to write it
    "tree.operand.bytes", "tree.operand.blocks",
    # the layout a tree fit staged its bin matrix on
    # (tree_impl.stage_tree_data): fit.shards += devices that hold a shard,
    # fit.shard_rows_max += rows (padding included) on the fullest. Read as
    # deltas over a window's fits: what `num_workers` came to on the mesh
    "fit.shards", "fit.shard_rows_max",
    # the fit-time column plan (ml/_column_plan.py, featurizer.try_fast_fit):
    # fits that took the plan / fits that fell through to the generic
    # sequential fit (the reason rides the event of the same name) /
    # columns that ran the sequential per-column code inside their job /
    # pieces (the frame's partitions, or its one table) the jobs read / fits
    # that made the frame's table-wide concat (the plan declined, or the
    # frame is one partition: a plan fit of several pieces makes none)
    "featurize.plan.fits", "featurize.plan.declined",
    "featurize.plan.columns_legacy", "featurize.plan.pieces",
    "featurize.collect.concats",
    # a tree fit's `_extract`: every label finite, so X is the block it was
    # handed (the column plan's, not copied) / a label was not, so the
    # rows with a finite one were gathered into a new block
    "featurize.extract.whole", "featurize.extract.gathered",
    # the quantize plan (tree_impl.make_bins: a job a column for the bin
    # statistics, a job a block of rows for the bins): a make_bins that ran
    # its jobs on the column plan's pool / one that ran them on the caller
    # (few rows, or the caller is itself a pool worker)
    "quantize.plan.fits", "quantize.plan.inline",
    # the fused logistic fit (linear_impl.fit_logistic_compact): programs
    # run / Newton steps the device executed (the loop's own count: it
    # stops at convergence, at most maxIter) / steps that moved the
    # coefficients (what a fit reports; every executed step does)
    "linear.irls.fits", "linear.irls.steps_run", "linear.irls.iterations",
    # the penalized fused fit (linear_impl._compact_enet_fn): inner
    # coordinate sweeps of its proximal steps / fits that ran maxIter
    # steps without converging / fits whose steps stopped shrinking at
    # float32's floor before one was under tol (linear_impl._stalled:
    # ended there, not converged); and fits that took the host loop, a
    # dispatch a step (linear_impl.fit_logistic: no compact block)
    "linear.irls.prox_sweeps", "linear.irls.unconverged",
    "linear.irls.floor_ended", "linear.host_loops",
    # the compact form's margin pass (featurizer.CompactParts.predict_affine:
    # a job a block of rows; the logistic summary inside `fit.summary`, a
    # linear summary's MAE when it is read): a pass that ran its jobs on the
    # column plan's pool / one that ran them on the caller
    "linear.summary.pooled", "linear.summary.inline",
    # prewarm manifest (parallel/prewarm.py): recorded signatures,
    # replayed/failed first-dispatches, pool-size attribution
    "prewarm.*",
    "dispatch.route_*",   # dispatch.route_host / dispatch.route_device
    "collective.*",       # per-trace collective launch counts PLUS the
                          # per-op payload-byte counters
                          # (collective.psum_bytes / pmean_bytes / ...):
                          # one launch's ICI allreduce volume, recorded at
                          # trace time from the operand's static shape —
                          # the *_bytes suffix puts them on the trace
                          # exporter's counter tracks
    # serving layer (sml_tpu/serving): request admission, micro-batch
    # dispatches, degradation ladder, model cache, canary mirror
    "serve.requests", "serve.rows",
    "serve.batches", "serve.batch_rows", "serve.batch_pad_rows",
    "serve.shed", "serve.expired", "serve.host_routed",
    # reason-tagged shed attribution next to the serve.shed total:
    # serve.shed.overflow (queue saturated, host fallback off) /
    # serve.shed.deadline (expired before its batch flushed) /
    # serve.shed.closed (submitted to a closing batcher) — so
    # engine_health()["shed"] and the fleet router see shed rate per
    # CAUSE, not one undifferentiated count
    "serve.shed.*",
    "serve.hot_swap",
    # a caller's BOUNDED result(timeout=) wait expired before the batch
    # resolved the future (serving/_batcher.py RequestTimeout): the
    # future stays resolvable — this counts impatient callers, not
    # dropped requests, distinct from serve.expired (deadline sheds)
    "serve.timeout",
    "serve.model_cache_hit", "serve.model_cache_miss",
    "serve.model_cache_evict_bytes",
    "serve.canary_mirrored",
    # canary shadow scores that DIED (the _mirror worker raised): a dead
    # canary must show up in canary_stats()/health_report() instead of
    # silently reporting zero divergence
    "serve.canary_error",
    # model & data drift (obs/drift.py): drift.chunk_flagged counts
    # ingest chunks whose sketch drifted past threshold (the
    # refit-trigger signal); drift.observe_error counts serving
    # observation callbacks that raised (observation must never fail a
    # flush, but a dead observer must be visible)
    "drift.*",
    # continuous training (sml_tpu/ct): ct.cycles / ct.refit_warm /
    # ct.refit_full / ct.promotions / ct.rollbacks (gate outcomes
    # applied to the registry) / ct.gate_pass / ct.gate_fail (verdicts)
    # / ct.checkpoints / ct.resumes (round-level boost restartability)
    # / ct.cycle_error (background-loop cycles that raised — the loop
    # survives, the failure is visible)
    "ct.*",
    # elastic multi-host fits (sml_tpu/ct/_elastic.py): elastic.resume
    # (one HostPreempted caught and resumed from the newest round-level
    # checkpoint) / elastic.repartition (the chunk ranges re-split to
    # the surviving host-group count) — paired 1:1 today, kept separate
    # so a future rebalance-without-preemption path counts honestly
    "elastic.*",
    # multi-replica serving fleet (sml_tpu/fleet): fleet.requests /
    # fleet.requests.<class> (router admissions by priority class) /
    # fleet.shed + fleet.shed.<class> (router-level priority sheds) /
    # fleet.reroutes (requests re-routed off a dead replica) /
    # fleet.replicas_started / fleet.replicas_evicted /
    # fleet.scale_up / fleet.scale_down (autoscaler band actions) /
    # fleet.autoscale_error (background steps that raised — the loop
    # survives, the failure is visible) / fleet.rollouts /
    # fleet.rollout_promotions / fleet.rollout_rollbacks (staged
    # rollout outcomes) / fleet.burst_tighten (admission pre-tightened
    # because the burn-rate SLOPE predicted an SLO breach within
    # sml.fleet.burstSlopeHorizonSec — burst anticipation)
    "fleet.*",
    # registry stage-transition listeners that RAISED (the commit
    # landed; later listeners still fired): a dead subscriber must be
    # visible in the counters, like serve.canary_error
    "tracking.listener_error",
    # open-loop trace-driven load harness (sml_tpu/loadgen): load.requests
    # / load.served / load.shed / load.timeout / load.errors fired per
    # scheduled request outcome, and load.overrun — requests the bounded
    # worker pool fired LATER than their scheduled arrival instant (the
    # schedule outran the pool; never silent, the committed gate requires
    # zero)
    "load.*",
}

GAUGES = {
    "hbm.*",              # hbm.<pool>_bytes / hbm.total_bytes
    "process.*",          # facts of the process, which no reset()
                          # drops: process.age_at_import_s (the
                          # process's age when `sml_tpu` started to
                          # import: the interpreter, the caller's own
                          # imports, jax and the runtime's start where
                          # they came first) / process.import_s (the
                          # package's import, wall seconds)
    "serve.queue_rows",   # rows admitted but not yet dispatched
    "serve.flush_micros",  # the micro-batcher's LIVE flush deadline —
                          # conf-static unless sml.serve.flushAutoTune
                          # adapts it between the audit's predicted
                          # drain and the SLO budget
    "slo.*",              # slo.burn_rate: breach fraction vs the
                          # sml.serve.sloMillis error budget, stamped by
                          # obs.engine_health()
    "drift.*",            # drift.max_severity / drift.features_flagged:
                          # the worst live-vs-baseline distance (as a
                          # multiple of its noise-aware threshold) and
                          # the flagged-feature count, stamped by every
                          # DriftMonitor.report()
    "fleet.*",            # fleet.replicas (live replica count, stamped
                          # on every pool topology change) /
                          # fleet.occupancy (the autoscaler's band
                          # signal at each step)
}

EVENTS = {
    "dispatch.*",         # dispatch.host / dispatch.device
    "featurize.plan.declined",  # why a fit did not take the column plan
                          # (args: reason), beside the counter of that name
    "fit.slow",           # a fit's verdict when its root closes (obs/_fits.py):
                          # the record, its shape's median, the phases by
                          # their excess over their medians, largest first
    "cache.*",            # cache.evict / ...
    "collective.*",       # collective.psum / ...
    "compile.*",          # compile.trace / compile.cache_dir
    "serve.*",            # serve.swap (endpoint hot-swap receipts)
    "infer.*",            # infer.dispatch / infer.drain (batch pipelining)
                          # + infer.kernel.spec (a scoring dispatch's
                          # resolved traversal spec CHANGED: kernel,
                          # block_rows)
    "ingest.*",           # ingest.dispatch / ingest.drain (chunk-i+1
                          # H2D overlapping chunk-i device work — the
                          # double-buffered prefetch proof) + ingest.note
                          # (per-chunk skew attribution summaries)
    "prewarm.*",          # prewarm.start / prewarm.replay / prewarm.done
    "skew.*",             # skew.note (per-program attribution summary)
                          # plus the skew.compute/skew.wait per-device
                          # lanes emitted as kind="span" through the raw
                          # RECORDER.emit path
    "health.*",           # health.snapshot (engine_health() receipts)
    # causal tracing (obs/_context.py): trace.request admission spans
    # (emitted as kind="span" so the exporter lands them on the
    # admitting thread's lane — the flow arrows' source anchor). Trace
    # ids themselves are not names: they ride event args ("trace",
    # "span", "parent_traces", "parent_spans") and METRICS observations
    # as per-bucket EXEMPLARS, so no registry entry can rot
    "trace.*",
    # stall watchdog (obs/_watchdog.py): stall.detected (with all-thread
    # stack snapshot args) / stall.resolved
    "stall.*",
    # black-box postmortem (obs/blackbox.py): blackbox.dump receipts
    "blackbox.*",
    # model & data drift (obs/drift.py): drift.report (per-monitor
    # verdict receipts with the flagged-feature list) and drift.chunk
    # (one ingest chunk's sketch judged against the baseline)
    "drift.*",
    # continuous training (sml_tpu/ct): ct.cycle (one trainer cycle's
    # action receipt), ct.refit (a scheduled warm/full refit),
    # ct.promote (canary gate passed — Production moved), ct.rollback
    # (gate failed — candidate archived, blackbox bundle path in args)
    "ct.*",
    # elastic multi-host fits (sml_tpu/ct/_elastic.py): elastic.resume
    # receipts carrying from_hosts/to_hosts, the dead group, and the
    # rows whose host assignment moved under the re-partition
    "elastic.*",
    # multi-replica serving fleet (sml_tpu/fleet): fleet.route (one
    # router decision: replica, priority class, the request's trace id
    # — the router half of the fan-in chain) / fleet.reroute (a
    # request re-routed off a dead replica, old + new trace ids) /
    # fleet.replica_start / fleet.replica_evict (teardown receipts,
    # blackbox bundle path in args) / fleet.scale (autoscaler band
    # action receipts) / fleet.rollout_stage (one replica's gate
    # verdict during a staged rollout) / fleet.rollout (the rollout's
    # final promote/rollback verdict)
    "fleet.*",
    # open-loop load harness (sml_tpu/loadgen): load.phase (the replay
    # driver crossing a trace-phase boundary) / load.run (one driver
    # run's outcome receipt: requests, overruns, per-phase counts)
    "load.*",
}

# streaming-metrics histograms (obs/_metrics.py METRICS.observe): latency
# and size distributions kept as log-bucketed counts, NOT recorder events
METRICS_NAMES = {
    "serve.request_ms",   # micro-batcher admission -> result per request
    "serve.batch_ms",     # one flush's launch+drain wall at the flush
                          # site — the drain floor the flush auto-tuner
                          # reads (sml.serve.flushAutoTune), exemplar =
                          # the flush's fan-in trace id
    "serve.canary_abs_diff",  # per mirrored request: max |shadow -
                          # primary| prediction divergence, exemplar =
                          # the request's trace id — canary_stats()
                          # reports windowed quantiles and the literal
                          # worst-diverging request from this histogram
    "dispatch.*",         # dispatch.host_ms / dispatch.device_ms: measured
                          # walls of routed programs (fed by the audit's
                          # attach path)
    "fit.wall_ms",        # a root fit's wall, one observation a record,
                          # exemplar = the fit's trace id: the slowest
                          # fit's trace through engine_health()["metrics"]
    "load.*",             # open-loop harness latencies, SCHEDULED-arrival
                          # -> result (queueing charged to the system, not
                          # hidden in the client): load.request_ms plus the
                          # per-phase load.request_ms.<phase> and
                          # per-phase/class load.request_ms.<phase>.<class>
                          # families, exemplar = the request's trace id
}

_BY_KIND = {"span": SPANS, "count": COUNTERS, "counter": COUNTERS,
            "gauge": GAUGES, "emit": EVENTS, "observe": METRICS_NAMES}


def _match(name: str, registry: Iterable[str]) -> bool:
    for entry in registry:
        if entry.endswith("*"):
            if name.startswith(entry[:-1]):
                return True
        elif name == entry:
            return True
    return False


def is_registered(kind: str, name: str) -> bool:
    """Exact-name check (`kind` is the call-site method: span / count /
    counter / gauge / emit)."""
    reg = _BY_KIND.get(kind)
    return reg is not None and _match(name, reg)


def prefix_registered(kind: str, prefix: str) -> bool:
    """f-string check: the literal prefix before the first interpolation
    must sit under some wildcard entry (a dynamic suffix can only be
    legal when the family itself is registered)."""
    reg = _BY_KIND.get(kind)
    if reg is None:
        return False
    for entry in reg:
        if entry.endswith("*") and prefix.startswith(entry[:-1]):
            return True
    return False
