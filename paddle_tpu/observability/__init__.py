"""paddle_tpu.observability — structured metrics + step timeline.

The signals that matter for a framework whose whole Program executes as
ONE fused XLA computation: compile events and compile-cache behavior
(executor.py), per-step host/device time and feed/fetch volumes
(Executor.run / run_loop / ParallelExecutor.run), serving latency and
batch-size distribution (Predictor / PredictorServer), and the
benchmark's per-run accounting (benchmark/lib). Everything records into
one process-wide ``MetricRegistry`` (metrics.py) and one bounded
``StepTimeline``
(timeline.py: a ring of steps and a ring of executable acquisitions);
export.py renders Prometheus text / JSON, and
``PredictorServer.start_http()`` serves it at ``GET /metrics``.

The legacy ``paddle_tpu.profiler`` module is a compatibility shim over
this registry (its event table lives in the
``paddle_tpu_profiler_event_ms`` summary).
"""
from __future__ import annotations

import time
import weakref
from typing import Optional

from . import export, metrics, timeline, tracing  # noqa: F401
from .metrics import (  # noqa: F401
    DEFAULT_SIZE_BUCKETS, MetricRegistry, REGISTRY, get_registry,
    process_labels, set_replica,
)
from .timeline import TIMELINE, StepTimeline, get_timeline, hlo_cost_stats  # noqa: F401

__all__ = [
    "REGISTRY", "TIMELINE", "get_registry", "get_timeline",
    "MetricRegistry", "StepTimeline", "metrics", "timeline", "export",
    "program_fp", "observe_run", "observe_acquire", "reset_all",
    "hlo_cost_stats", "nbytes_of",
    # shared instruments
    "COMPILE_TOTAL", "COMPILE_LATENCY_MS", "CACHE_HITS", "CACHE_MISSES",
    "DP_OWNED_STATE_BYTES",
    "CACHE_ENTRIES_FED", "CACHE_ENTRIES_ALIASED",
    "CACHE_EVICTIONS", "STEP_LATENCY_MS", "STEPS_TOTAL", "FEED_BYTES",
    "FETCH_BYTES", "RUN_LOOP_WINDOW_STEPS", "READER_PREFETCH_EVENTS",
    "READER_PREFETCH_DEPTH", "READER_PULL_MS", "LOADER_BATCHES",
    "LOADER_BLOCKED_MS", "LOADER_WORKER_BUSY_MS", "LOADER_QUEUE_DEPTH",
    "LOADER_WORKERS", "PREDICT_LATENCY_MS", "PREDICT_REQUESTS",
    "PREDICT_BATCH_ROWS", "PREDICT_FAILURES", "PROFILER_EVENT_MS",
    "SERVER_ROWS", "SERVER_BUCKET_FILL",
    "SERVER_INFLIGHT_DEPTH", "SERVER_STAGE_MS", "AOT_CACHE_BYTES",
    "AOT_CACHE_WRITTEN_BYTES", "AOT_CACHE_EVICTIONS", "AOT_CACHE_CORRUPT",
    "AOT_CACHE_ERRORS", "AOT_COMPILE_MS", "ANALYSIS_ISSUES",
    "ANALYSIS_COVERAGE", "set_replica", "process_labels",
    "FLEET_WORKERS", "FLEET_OUTSTANDING", "FLEET_DISPATCHES",
    "FLEET_REQUEUED", "FLEET_MISVERSIONED", "FLEET_BACKPRESSURE_MS",
    "FLEET_SHED", "FLEET_PENDING", "FLEET_AUTOSCALE",
    "DECODE_TOKENS", "DECODE_STEPS", "DECODE_SLOTS", "DECODE_STEP_MS",
    "DECODE_REQUESTS", "DECODE_ADMIT_DEFERRED", "DECODE_PRELOAD",
    "DECODE_PREFIX_QUERIES", "DECODE_PREFIX_HITS", "DECODE_PREFIX_BYTES",
    "DECODE_SPEC_PROPOSED", "DECODE_SPEC_ACCEPTED",
    "CKPT_SAVES", "CKPT_BYTES", "CKPT_PENDING", "CKPT_SAVE_MS",
    "CKPT_RESTORE_MS", "CKPT_RETRIES", "CKPT_FAILURES",
    "SWAP_TOTAL", "SWAP_MS", "TRAIN_SKIPPED_BATCHES", "FLEET_WEDGED",
    "REQUEST_PHASE_MS", "TRACE_SPANS", "tracing",
    "TRANSPILE_OPS_REMOVED", "TRANSPILE_OPS_FUSED", "TRANSPILE_PASS_MS",
    "QUANT_CALIB_BATCHES", "QUANT_OPS", "QUANT_PARITY",
    "FUSED_HEAD_TRACES", "MLA_TRACES", "SSM_SCAN_TRACES", "KDA_SCAN_TRACES",
    "KDA_STEP_TRACES",
    "PREFILL_ATTN_TRACES", "PREFILL_ATTN_FORMS", "MOE_TOKENS_ELSEWHERE",
]

# -- the shared instrument set (registered once, process-wide) -----------

COMPILE_TOTAL = REGISTRY.counter(
    "paddle_tpu_compile_total",
    "Program compilations (trace + XLA compile), by executor kind")
COMPILE_LATENCY_MS = REGISTRY.histogram(
    "paddle_tpu_compile_latency_ms",
    "Wall time of each compilation (first call: trace+compile+run)")
CACHE_ENTRIES_FED = REGISTRY.counter(
    "paddle_tpu_decode_cache_entries_fed_total",
    "Cache entries (KV slabs, scales, states) fed to the donating decode "
    "programs acquired, by kind")
CACHE_ENTRIES_ALIASED = REGISTRY.counter(
    "paddle_tpu_decode_cache_entries_aliased_total",
    "Of those, the entries whose update the compiled program writes into "
    "a donated feed's buffer (its input_output_alias), by kind: equal to "
    "the fed count on a chip, 0 on the CPU where nothing is donated")
FUSED_HEAD_TRACES = REGISTRY.counter(
    "paddle_tpu_fused_head_traces_total",
    "Traces of the fused LM head (ops/fused_loss.py), by "
    "path=vocab_parallel|local and the ways the vocabulary is split: "
    "which path a step was compiled with. Counted when the op is traced, "
    "so a step loaded from the executable cache adds nothing")
MLA_TRACES = REGISTRY.counter(
    "paddle_tpu_mla_traces_total",
    "Traces of latent attention (ops/mla.py), by path=expanded (a "
    "prefill: K and V of every head built from the latent rows) | "
    "absorbed_kernel_once | absorbed_kernel | absorbed (a step: attention "
    "ON the latent rows, by the kernel over live blocks, each fetched once "
    "and kept in VMEM between its two passes | the same, fetched a pass: "
    "under a choice of rows, or a slot too large to keep | the lax form "
    "over whole slabs). "
    "Counted when the op is traced: a program loaded from a cache adds 0")
SSM_SCAN_TRACES = REGISTRY.counter(
    "paddle_tpu_ssm_scan_traces_total",
    "Traces of the selective scan (ops/ssm.py), by path=kernel (one "
    "Pallas call whose state tile stays in vector memory and whose grid "
    "stops at a row's length) | lax (a lax.scan over every position of "
    "the bucket: the CPU, a bucket under one block of positions). "
    "Counted when the op is traced: a program loaded from a cache adds 0")
KDA_SCAN_TRACES = REGISTRY.counter(
    "paddle_tpu_kda_scan_traces_total",
    "Traces of the chunked delta rule (ops/kda.py), by path=kernel (one "
    "Pallas call whose matrix state and chunk factors stay in vector "
    "memory and whose grid stops at a row's length) | lax (composed lax "
    "over every chunk of the bucket: the CPU, a shape the kernel does "
    "not take) and by form=factored (a gate whose bound keeps 16 tokens "
    "inside float32) | guarded (a gate with no such bound: a sub-chunk's "
    "own block by e^(G_t - G_i) itself). Counted when the op is traced: "
    "a program loaded from a cache adds 0")
KDA_STEP_TRACES = REGISTRY.counter(
    "paddle_tpu_kda_step_traces_total",
    "Traces of the delta rule's one-token step (ops/kda.py), by "
    "path=kernel (one Pallas call a layer: a block of heads' matrix "
    "states read once, updated in vector memory and written once over "
    "their own input) | lax (exact float32 lax that passes over a state "
    "twice and a half: the CPU, a state that is not float32 or not whole "
    "128 x 128 tiles). Counted when the op is traced: a program loaded "
    "from a cache adds 0")
PREFILL_ATTN_TRACES = REGISTRY.counter(
    "paddle_tpu_prefill_attn_traces_total",
    "Traces of a serving prefill's causal attention (ops/attention.py: "
    "prefill_attention), by path=kernel (the flash forward: a TPU, a "
    "block-aligned bucket of 256 rows and up) | lax (the exact form "
    "over (T, T) scores), operands=the type the products' operands "
    "have (bfloat16 on the kernel path whatever came in, else the "
    "caller's) and lengths=given (the kernel skips the q-blocks past a "
    "row's length) | none. Counted when the op is traced: a program "
    "loaded from a cache adds 0")
PREFILL_ATTN_FORMS = REGISTRY.counter(
    "paddle_tpu_prefill_attn_forms_total",
    "Traces of a serving prefill's causal attention that are not the "
    "plain form (a window under the bucket, a sink, fewer key/value "
    "heads than query heads, or V narrower than q and K), beside "
    "paddle_tpu_prefill_attn_traces_total: sink=learned (a scalar a "
    "query head joins the softmax's denominator: on the kernel path the "
    "last write times sigmoid(lse - sink), inside the call) | none, "
    "value_width=own (V's heads narrower than q's and K's: 192 / 192 / "
    "128) | query, kv=own (K and V handed over at their own head count, "
    "query head hi reading head hi // group: nothing repeated) | query "
    "(one head count), block_k=the kernel's key block, which follows the "
    "window (128 under a window of 128, else 512) | none (the lax form). "
    "Counted when the op is traced: a program loaded from a cache adds 0")
CACHE_HITS = REGISTRY.counter(
    "paddle_tpu_compile_cache_hits_total",
    "Compile-cache hits, by kind, program fingerprint, and "
    "tier=memory|disk (disk = persistent AOT executable store)")
CACHE_MISSES = REGISTRY.counter(
    "paddle_tpu_compile_cache_misses_total",
    "Compile-cache misses, by kind, program fingerprint, and "
    "tier=memory|disk")
DP_OWNED_STATE_BYTES = REGISTRY.gauge(
    "paddle_tpu_parallel_dp_owned_state_bytes",
    "ParallelExecutor, set when a step is compiled, by program fingerprint: "
    "of=state the bytes of the step's persistable state, of=owned those "
    "of them that the plan splits over a batch axis wider than 1, so that "
    "one data-parallel rank owns their update (0 on a replicated plan and "
    "on a mesh with no such axis)")
CACHE_EVICTIONS = REGISTRY.counter(
    "paddle_tpu_compile_cache_evictions_total",
    "Compile-cache LRU evictions (cap: PADDLE_TPU_COMPILE_CACHE_MAX)")
STEP_LATENCY_MS = REGISTRY.histogram(
    "paddle_tpu_step_latency_ms",
    "Wall time per executor dispatch (run: one step; loop: one window)")
STEPS_TOTAL = REGISTRY.counter(
    "paddle_tpu_steps_total", "Training/inference steps executed")
FEED_BYTES = REGISTRY.counter(
    "paddle_tpu_feed_bytes_total", "Bytes fed into executed programs")
FETCH_BYTES = REGISTRY.counter(
    "paddle_tpu_fetch_bytes_total", "Bytes fetched out of executed programs")
RUN_LOOP_WINDOW_STEPS = REGISTRY.histogram(
    "paddle_tpu_run_loop_window_steps",
    "Per-call reader/loop window length (truncation shows up as mass "
    "below `steps`)", buckets=DEFAULT_SIZE_BUCKETS)
READER_PREFETCH_EVENTS = REGISTRY.counter(
    "paddle_tpu_reader_prefetch_events_total",
    "Reader double-buffer lifecycle: staged / used / flushed / error")
READER_PREFETCH_DEPTH = REGISTRY.gauge(
    "paddle_tpu_reader_prefetch_depth",
    "Programs with a device-staged next window right now")
READER_PULL_MS = REGISTRY.counter(
    "paddle_tpu_reader_pull_ms_total",
    "Host time the executor spent pulling reader batches before dispatch, "
    "by kind=run|loop (input-bound when this rivals step latency)")
LOADER_BATCHES = REGISTRY.counter(
    "paddle_tpu_loader_batches_total",
    "DataLoader batches delivered, by loader and transport="
    "shm|pickle|inline (pickle = batch outgrew the slot or object dtype)")
LOADER_BLOCKED_MS = REGISTRY.counter(
    "paddle_tpu_loader_blocked_ms_total",
    "Time DataLoader consumers spent blocked in next() (starvation "
    "fraction = this / wall time)")
LOADER_WORKER_BUSY_MS = REGISTRY.counter(
    "paddle_tpu_loader_worker_busy_ms_total",
    "Summed DataLoader worker decode+assemble time (utilization = this / "
    "(workers x wall time))")
LOADER_QUEUE_DEPTH = REGISTRY.gauge(
    "paddle_tpu_loader_queue_depth",
    "Ready DataLoader batches buffered consumer-side right now "
    "(0 while blocked = workers can't keep up)")
LOADER_WORKERS = REGISTRY.gauge(
    "paddle_tpu_loader_workers", "Worker processes per running DataLoader")
PREDICT_LATENCY_MS = REGISTRY.histogram(
    "paddle_tpu_predict_latency_ms",
    "Predictor request latency (path=direct|server; server includes queue "
    "wait)")
PREDICT_REQUESTS = REGISTRY.counter(
    "paddle_tpu_predict_requests_total", "Predictor requests served")
PREDICT_BATCH_ROWS = REGISTRY.histogram(
    "paddle_tpu_predict_batch_rows",
    "Rows per executed predict batch (server: dynamic batch fill)",
    buckets=DEFAULT_SIZE_BUCKETS)
PREDICT_FAILURES = REGISTRY.counter(
    "paddle_tpu_predict_failures_total",
    "Predict requests completed with an error, by path (error rate = "
    "this / paddle_tpu_predict_requests_total)")
SERVER_ROWS = REGISTRY.counter(
    "paddle_tpu_server_rows_total",
    "Rows through the serving device stage, kind=real|pad "
    "(pad-waste ratio = pad / (real + pad))")
SERVER_BUCKET_FILL = REGISTRY.histogram(
    "paddle_tpu_server_bucket_fill",
    "Real rows per executed server batch, labeled by the padded bucket "
    "size it ran at (fill efficiency per compiled signature)",
    buckets=DEFAULT_SIZE_BUCKETS)
SERVER_INFLIGHT_DEPTH = REGISTRY.gauge(
    "paddle_tpu_server_inflight_depth",
    "Stacked batches waiting for the serving device stage right now "
    "(0 = device-bound, at capacity = host-bound)")
SERVER_STAGE_MS = REGISTRY.histogram(
    "paddle_tpu_server_stage_ms",
    "Per-batch wall time of each serving pipeline stage "
    "(stage=stack|device)")
AOT_CACHE_BYTES = REGISTRY.gauge(
    "paddle_tpu_aot_cache_bytes",
    "On-disk size of the persistent AOT executable cache after the last "
    "store/GC, by cache dir")
AOT_CACHE_WRITTEN_BYTES = REGISTRY.counter(
    "paddle_tpu_aot_cache_written_bytes_total",
    "Serialized executable bytes written to the AOT disk cache")
AOT_CACHE_EVICTIONS = REGISTRY.counter(
    "paddle_tpu_aot_cache_evictions_total",
    "AOT disk-cache entries evicted by the mtime-LRU GC "
    "(bound: PADDLE_TPU_AOT_CACHE_MAX_BYTES)")
AOT_CACHE_CORRUPT = REGISTRY.counter(
    "paddle_tpu_aot_cache_corrupt_total",
    "Unreadable AOT cache payloads, reason=blob|sidecar (blobs are "
    "quarantined *.corrupt and recompiled — never a crash)")
AOT_CACHE_ERRORS = REGISTRY.counter(
    "paddle_tpu_aot_cache_errors_total",
    "AOT disk-cache operations that degraded to compile-only, by "
    "op=serialize|store (e.g. read-only cache dir)")
AOT_COMPILE_MS = REGISTRY.histogram(
    "paddle_tpu_aot_compile_ms",
    "Executable acquisition wall time on the AOT path, by kind and "
    "path=cold (explicit lower+XLA compile) | warm (disk deserialize) — "
    "the cold-start-vs-warm-start distribution")
ANALYSIS_ISSUES = REGISTRY.counter(
    "paddle_tpu_analysis_issues_total",
    "Static-analyzer findings, by diagnostic code and severity "
    "(analysis/: shape-mismatch, use-before-def, tpu-dynamic-shape, "
    "recompile-risk, dead-op, ...)")
ANALYSIS_COVERAGE = REGISTRY.gauge(
    "paddle_tpu_analysis_infer_coverage",
    "Fraction of a program's op instances covered by a registered "
    "shape/dtype inference rule, per program fingerprint")
TRANSPILE_OPS_REMOVED = REGISTRY.counter(
    "paddle_tpu_transpile_ops_removed_total",
    "Ops deleted by the optimizing transpiler, by pass="
    "constant_fold|cse|dce|conv_bn_fold (transpiler/passes/)")
TRANSPILE_OPS_FUSED = REGISTRY.counter(
    "paddle_tpu_transpile_ops_fused_total",
    "Source ops folded INTO a fused op by the fusion passes, by pass "
    "(3 means mul+elementwise_add+relu became one fused_fc)")
TRANSPILE_PASS_MS = REGISTRY.histogram(
    "paddle_tpu_transpile_passes_ms",
    "Wall time per optimizing-transpiler pass invocation, by pass")
QUANT_CALIB_BATCHES = REGISTRY.counter(
    "paddle_tpu_quant_calib_batches_total",
    "Sample batches streamed through quant.calibrate (activation-amax "
    "collection for int8 post-training quantization)")
QUANT_OPS = REGISTRY.counter(
    "paddle_tpu_quant_quantized_ops_total",
    "Ops the level-3 quantize pass rewrote onto int8 kernels, by the "
    "source op type (op=mul|matmul|fused_fc|conv2d)")
QUANT_PARITY = REGISTRY.gauge(
    "paddle_tpu_quant_parity_max_abs_diff",
    "Max abs logits difference of the last quant.parity_report run "
    "(quantized vs float on the same feeds) — the drift the int8 tier "
    "is currently serving at")
FLEET_WORKERS = REGISTRY.gauge(
    "paddle_tpu_fleet_workers",
    "Router view of worker replicas by state=starting|ready|draining|"
    "stopped|dead (recorded in the ROUTER process)")
FLEET_OUTSTANDING = REGISTRY.gauge(
    "paddle_tpu_fleet_outstanding",
    "Requests dispatched to a replica and not yet answered, by replica "
    "(at max_outstanding on every replica = fleet saturated, router "
    "backpressures)")
FLEET_DISPATCHES = REGISTRY.counter(
    "paddle_tpu_fleet_dispatches_total",
    "Request frames the router forwarded, by replica (balance skew = "
    "max/min across replicas)")
FLEET_REQUEUED = REGISTRY.counter(
    "paddle_tpu_fleet_requeued_total",
    "In-flight frames re-dispatched after their worker died (predict is "
    "stateless/idempotent, so replay is safe)")
FLEET_MISVERSIONED = REGISTRY.counter(
    "paddle_tpu_fleet_misversioned_total",
    "Responses whose program version differed from the one their "
    "request was dispatched under (must stay 0 through drain/restart "
    "and hot swaps)")
FLEET_BACKPRESSURE_MS = REGISTRY.counter(
    "paddle_tpu_fleet_backpressure_ms_total",
    "Router dispatch time blocked because every routable replica was at "
    "max_outstanding (rivaling wall time = add replicas or raise the "
    "window)")
FLEET_SHED = REGISTRY.counter(
    "paddle_tpu_fleet_shed_total",
    "Requests rejected by bounded-latency load shedding, by SLO class — "
    "every shed is an explicit structured RejectedError to the client, "
    "never a timeout (nonzero = the fleet is declining work to protect "
    "deadlines: add replicas or lower the offered load)")
FLEET_PENDING = REGISTRY.gauge(
    "paddle_tpu_fleet_pending",
    "Requests waiting in the router's priority dispatch queue right now, "
    "by SLO class (growing while replicas idle = dispatch-bound; growing "
    "at max_outstanding everywhere = fleet saturated)")
FLEET_AUTOSCALE = REGISTRY.counter(
    "paddle_tpu_fleet_autoscale_total",
    "Autoscaler actions, by direction=up (replica added) | down "
    "(drain-shrink) | heal (dead replica reaped and replaced)")
DECODE_TOKENS = REGISTRY.counter(
    "paddle_tpu_decode_tokens_total",
    "Tokens generated by the KV-cache decode path, by kind=prefill "
    "(prompt tokens absorbed) | decode (sampled tokens)")
DECODE_STEPS = REGISTRY.counter(
    "paddle_tpu_decode_steps_total",
    "Decode steps the serving loop dispatched, by in_flight=1 (the step "
    "before it was still unread: the device had its next step queued "
    "before the host saw a token) | 0 (the first step after a park, a "
    "failure or an emptied batch); the share of 1 is how often the "
    "per-token round trip is hidden")
MOE_EXPERT_PAIRS = REGISTRY.counter(
    "paddle_tpu_moe_expert_pairs_total",
    "Token-expert pairs the decode path routed to the experts it holds "
    "(every one computed: the serving expert layer drops none), by layer")
EVA_ROWS = REGISTRY.counter(
    "paddle_tpu_eva_rows_total",
    "Rows one EVA layer's attention read in decode steps, by kind: "
    "window (a live slot's exact keys, its own window's up to its "
    "position) | summary (the pooled rows of the windows closed before "
    "it, one a chunk)")
MOE_TOKENS_ELSEWHERE = REGISTRY.counter(
    "paddle_tpu_moe_tokens_elsewhere_total",
    "Real tokens that sent the experts held here NO pair, by layer: "
    "under group-limited routing with a group a chip, a token whose "
    "kept groups leave this chip's out (half of all tokens where 4 of 8 "
    "groups are kept and the groups are even)")
MOE_LOAD_MAX_OVER_MEAN = REGISTRY.gauge(
    "paddle_tpu_moe_load_max_over_mean",
    "The busiest held expert's pairs over the mean of the held experts', "
    "over a decode server's life, by layer (1 = even; an expert-parallel "
    "deployment waits for its busiest chip)")
DECODE_SLOTS = REGISTRY.gauge(
    "paddle_tpu_decode_slots",
    "Continuous-batching cache-slot occupancy, state=active|free "
    "(active at the slot cap with a non-empty admission queue = grow "
    "slots or add replicas)")
DECODE_STEP_MS = REGISTRY.histogram(
    "paddle_tpu_decode_step_ms",
    "Wall time per decode iteration, stage=prefill (a server's "
    "admission sub-batch, from its prefill's dispatch to its logits on "
    "the host: the decode.loop.prefill and first_token phases' sum; "
    "DecodePredictor.generate's own prefill: the dispatch alone) | step "
    "(one token across every active slot: from the "
    "token before it reaching the host, or from its own dispatch where "
    "no step was in flight, to its token reaching the host)")
DECODE_REQUESTS = REGISTRY.counter(
    "paddle_tpu_decode_requests_total",
    "Decode-serving sequences, kind=admitted (entered a cache slot) | "
    "retired (finished and freed it); admitted - retired = in flight")
DECODE_ADMIT_DEFERRED = REGISTRY.counter(
    "paddle_tpu_decode_admit_deferred_total",
    "Queued requests that had a free slot within an admission's room and "
    "were left for the next iteration (DecodeServer._admit_group): their "
    "prompt's length bucket is not the oldest request's, or the token "
    "bound or the whole-batch rule cut the group; each keeps its place and "
    "waits one decode step: the price of prefills without padding")
DECODE_PRELOAD = REGISTRY.counter(
    "paddle_tpu_decode_preload_total",
    "Prefill executables a decode predictor's disk directory named when "
    "its first server started (DecodePredictor.preload, on the caller's "
    "thread, before the loop opens), by result=loaded (through "
    "Engine.acquire: a path=warm record under the phase decode.preload) "
    "| stale (the sidecar re-hashes to another key: another program, "
    "environment or jax) | unreadable (the blob would not load: "
    "quarantined, compiled again at its first admission). A shape "
    "already in memory counts nothing, and nothing is ever compiled "
    "here")
DECODE_PREFIX_QUERIES = REGISTRY.counter(
    "paddle_tpu_decode_prefix_queries_total",
    "Shared-prefix store lookups at admission (one per admitted "
    "prompt when prefix sharing is on)")
DECODE_PREFIX_HITS = REGISTRY.counter(
    "paddle_tpu_decode_prefix_hits_total",
    "Shared-prefix store hits, by kind=full (whole prompt served from "
    "cached K/V rows) | partial (cached header + suffix extension) | "
    "batch (deduped against an identical prompt admitted in the same "
    "sub-batch); hit rate = hits / queries — the ROADMAP-named signal")
DECODE_PREFIX_BYTES = REGISTRY.gauge(
    "paddle_tpu_decode_prefix_bytes",
    "Resident bytes of prefilled K/V rows in the shared-prefix store "
    "(bounded by PADDLE_TPU_PREFIX_CACHE_MAX_BYTES; refcounted entries "
    "are eviction-exempt while sequences decode from them)")
DECODE_SPEC_PROPOSED = REGISTRY.counter(
    "paddle_tpu_decode_spec_proposed_total",
    "Draft tokens proposed to speculative verify windows")
DECODE_SPEC_ACCEPTED = REGISTRY.counter(
    "paddle_tpu_decode_spec_accepted_total",
    "Draft tokens the target accepted; acceptance rate = accepted / "
    "proposed — the signal that decides whether speculation pays "
    "(each verified round also emits one bonus token not counted here)")
CKPT_SAVES = REGISTRY.counter(
    "paddle_tpu_ckpt_saves_total",
    "Checkpoint saves, by mode=async|sync and result=ok|error (async = "
    "background writer off the step path; sync = degraded or explicit)")
CKPT_BYTES = REGISTRY.counter(
    "paddle_tpu_ckpt_bytes",
    "Bytes durably written into complete checkpoints (persistables npz "
    "+ meta + sentinel)")
CKPT_PENDING = REGISTRY.gauge(
    "paddle_tpu_ckpt_pending",
    "Snapshots queued for the background checkpoint writer right now "
    "(at max_pending = the trainer blocks: bounded staleness, never "
    "dropped saves)")
CKPT_SAVE_MS = REGISTRY.histogram(
    "paddle_tpu_ckpt_save_ms",
    "Wall time per checkpoint write, by mode=async (inside the writer "
    "thread, off the step path) | sync (paid by the training step) | "
    "snapshot (the on-step-path state copy an async save starts with)")
CKPT_RESTORE_MS = REGISTRY.histogram(
    "paddle_tpu_ckpt_restore_ms",
    "Wall time to load the newest complete checkpoint at resume")
CKPT_RETRIES = REGISTRY.counter(
    "paddle_tpu_ckpt_retries_total",
    "Checkpoint write attempts retried after a transient IO error "
    "(exponential backoff; exhaustion degrades the manager to "
    "synchronous saves)")
CKPT_FAILURES = REGISTRY.counter(
    "paddle_tpu_ckpt_failures_total",
    "Checkpoint saves that failed every retry — surfaced as a warning "
    "+ degraded mode, never silently skipped")
SWAP_TOTAL = REGISTRY.counter(
    "paddle_tpu_swap_total",
    "Hot model swaps through serving.swap.SwapController, by "
    "result=ok (version flipped, old replicas retired) | rollback "
    "(validation/spawn/canary/flip failure — the old version never "
    "stopped serving and the fleet is restored)")
SWAP_MS = REGISTRY.histogram(
    "paddle_tpu_swap_ms",
    "Wall time of hot-swap phases, phase=spawn (surge replicas on the "
    "new version, warm-AOT) | canary (live-request parity probes) | "
    "retire (drain + stop the old version) | total")
TRAIN_SKIPPED_BATCHES = REGISTRY.counter(
    "paddle_tpu_train_skipped_batches_total",
    "Input the hardened training data plane dropped instead of "
    "crashing or poisoning parameters, by reason=nonfinite (in-graph "
    "NaN/Inf sentinel zeroed the update and quarantined the batch) | "
    "corrupt_chunk (tolerant recordio chunk skip+resync) | "
    "corrupt_record (record whose payload no longer unpickles)")
FLEET_WEDGED = REGISTRY.counter(
    "paddle_tpu_fleet_wedged_total",
    "Live-but-hung replicas the router's watchdog reaped: outstanding "
    "work with no completion past wedge_timeout_s — the worker is "
    "SIGKILLed and its in-flight frames requeue exactly like a crash "
    "(nonzero = raise wedge_timeout_s or investigate stuck device "
    "dispatches)")
REQUEST_PHASE_MS = REGISTRY.histogram(
    "paddle_tpu_request_phase_ms",
    "Per-phase latency attribution of TRACED serving requests, by "
    "phase=queue (router admission -> dispatch) | service (dispatch -> "
    "reply, the whole worker round trip) | stack | device (the worker-"
    "side stages) | total (submit -> reply). Folded from trace spans as "
    "requests complete, so mass appears only while "
    "PADDLE_TPU_TRACE_SAMPLE > 0 — the attributed view of "
    "paddle_tpu_predict_latency_ms")
TRACE_SPANS = REGISTRY.counter(
    "paddle_tpu_trace_spans_total",
    "Trace spans recorded by this process's flight recorder, by "
    "phase=span name (client.submit, router.dispatch, worker.recv, "
    "server.device, decode.retire, ...) — nonzero means sampling is "
    "live; compare against the recorder's dropped count in /trace.json")
tracing._SPANS_TOTAL = TRACE_SPANS
PROFILER_EVENT_MS = REGISTRY.summary(
    "paddle_tpu_profiler_event_ms",
    "Legacy profiler event table (exact count/sum/min/max per event)")


# -- helpers -------------------------------------------------------------

# fingerprint cache: Program.fingerprint() json-serializes the whole
# program — fine once per compile, far too hot for once per step. Weak
# keys so a dead program's entry dies with it (same reasoning as the
# executor's per-program step counters).
_FP_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def program_fp(program) -> str:
    """Short (8-hex) fingerprint of a Program, cached per version."""
    try:
        entry = _FP_CACHE.get(program)
        version = getattr(program, "_version", None)
        if entry is None or entry[0] != version:
            entry = (version, program.fingerprint()[:8])
            _FP_CACHE[program] = entry
        return entry[1]
    except Exception:  # fingerprinting must never break execution
        return "%08x" % (id(program) & 0xFFFFFFFF)


def observe_run(kind: str, wall_s: float, *, steps: int = 1,
                program: Optional[str] = None, compiled: bool = False,
                lazy: bool = True, hlo: Optional[dict] = None,
                feed_bytes: int = 0, fetch_bytes: int = 0,
                device_ms: Optional[float] = None):
    """One executor dispatch -> registry + timeline, in one call (keeps
    the executor hot path to a single function call). ``compiled=True``
    marks a first call. With ``lazy`` the executable was a bare
    ``jax.jit`` and its trace+compile happened inside this call: the call
    IS the acquisition, and is recorded as one (``path="lazy"``; ``hlo``
    carries the opt-in trace/lower split and cost estimates from
    ``Executor._hlo_compile_stats``). Without it the executable came
    through ``Engine.acquire``, which wrote the record; the first
    dispatch still counts as a compilation, as it always has."""
    wall_ms = wall_s * 1e3
    STEP_LATENCY_MS.observe(wall_ms, kind=kind)
    STEPS_TOTAL.inc(steps, kind=kind)
    if feed_bytes:
        FEED_BYTES.inc(feed_bytes, kind=kind)
    if fetch_bytes:
        FETCH_BYTES.inc(fetch_bytes, kind=kind)
    if compiled and lazy:
        observe_acquire(kind, "lazy", wall_ms, program=program,
                        ts=time.time() - wall_s,
                        phase=tracing.current_phase(), compile_ms=wall_ms,
                        **(hlo or {}))
    elif compiled:
        _count_compile(kind, wall_ms)
    TIMELINE.record_step(kind, wall_ms, steps=steps, program=program,
                         device_ms=device_ms, feed_bytes=feed_bytes,
                         fetch_bytes=fetch_bytes)


def _count_compile(kind: str, ms: float):
    COMPILE_TOTAL.inc(kind=kind)
    COMPILE_LATENCY_MS.observe(ms, kind=kind)


# the timeline's `cache` field, older than `path` and kept beside it
_CACHE_OF_PATH = {"warm": "aot-load", "cold": "miss", "lazy": "miss"}


def observe_acquire(kind: str, path: str, wall_ms: float, *,
                    program: Optional[str] = None,
                    name: Optional[str] = None, ts: Optional[float] = None,
                    phase: Optional[str] = None,
                    aot_ms: Optional[float] = None, disk: bool = False,
                    compile_ms: Optional[float] = None, **fields):
    """One executable acquisition -> registry + timeline, in one call:
    the one place that feeds the compile instruments and writes the
    timeline's compile record. A hit in a memory cache is no acquisition
    and never comes here.

    The record: ``name`` (the executable's own, ``ptpu_<kind>_b<batch>_
    s<seq>`` as the device trace prints it, else ``<kind>/<program>``),
    ``kind``, ``program`` (8-hex fingerprint), ``path`` = ``"warm"`` (a
    hit in the AOT disk tier) | ``"cold"`` (lower + XLA compile) |
    ``"lazy"`` (a first call through ``jax.jit``: trace + compile + run,
    not split), ``cache`` (``"aot-load"`` | ``"miss"``), ``ts`` (the
    START, ``time.time()``: the flight recorder's clock) and ``wall_ms``
    (the whole acquisition as its caller saw it), ``phase`` (the
    innermost ``tracing.phase`` open on the thread when it began; absent
    when none was, as at trace rate 0), and in ``fields`` the parts that
    path has, each in ms and summing to no more than ``wall_ms``:
    ``build_ms`` (the caller's program construction, feed structs and
    key), ``load_ms`` (read + deserialize; with ``blob_bytes``),
    ``trace_ms``, ``xla_ms``, ``store_ms`` (serialize + write),
    ``describe_ms``; whatever else the caller read off the executable
    (``flops``, ``cache_fed``) rides along.

    The registry, each instrument as it always moved: ``aot_ms`` is the
    sample of ``paddle_tpu_aot_compile_ms{path, kind}`` (the AOT path
    only: a load's time, or trace + XLA); ``disk`` counts a hit (warm)
    or a miss in the disk tier; ``compile_ms`` counts a compilation and
    is its ``paddle_tpu_compile_latency_ms`` sample."""
    if aot_ms is not None:
        AOT_COMPILE_MS.observe(aot_ms, path=path, kind=kind)
    if disk:
        (CACHE_HITS if path == "warm" else CACHE_MISSES).inc(
            kind=kind, tier="disk", program=program)
    if compile_ms is not None:
        _count_compile(kind, compile_ms)
    TIMELINE.record_compile(
        kind, program, ts=ts, cache=_CACHE_OF_PATH[path],
        name=name or "%s/%s" % (kind, program), path=path,
        wall_ms=wall_ms, phase=phase, **fields)


def nbytes_of(values) -> int:
    """Total nbytes across an iterable of arrays (jax or numpy); values
    without a known size count 0 — accounting must never throw."""
    total = 0
    for v in values:
        n = getattr(v, "nbytes", None)
        if n is None:
            size = getattr(v, "size", None)
            itemsize = getattr(getattr(v, "dtype", None), "itemsize", None)
            n = size * itemsize if size is not None and itemsize else 0
        total += int(n)
    return total


def reset_all():
    """Zero the registry and clear the timeline + trace recorder (the
    registry-wide reset the legacy ``profiler.reset_profiler``
    delegates to)."""
    REGISTRY.reset()
    TIMELINE.reset()
    tracing.reset()
