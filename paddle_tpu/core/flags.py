"""Global flag registry.

Reference parity: paddle's gflags-compatible registry (paddle/common/flags.h:38,
flags.cc: 187 PHI_DEFINE_EXPORTED_* definitions) exposed through
paddle.set_flags/get_flags and FLAGS_* env vars. Same surface here; flags also
seed from the environment at import.
"""
from __future__ import annotations

import os
from typing import Any

_REGISTRY: dict[str, dict[str, Any]] = {}


def define_flag(name: str, default, doc: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    env = os.environ.get(name)
    value = default
    if env is not None:
        value = _parse(env, default)
    _REGISTRY[name] = {"value": value, "default": default, "doc": doc}
    return value


def _parse(text: str, default):
    if isinstance(default, bool):
        return text.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(text)
    if isinstance(default, float):
        return float(text)
    return text


#: bumped on every flag write — hot paths snapshot flag values keyed by
#: this generation instead of paying registry lookups per op (dispatch.py)
generation = 0


def set_flags(flags: dict):
    global generation
    for k, v in flags.items():
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        if k not in _REGISTRY:
            _REGISTRY[k] = {"value": v, "default": v, "doc": "(ad-hoc)"}
        else:
            _REGISTRY[k]["value"] = v
    # bump AFTER the writes: snapshot readers keyed on the generation must
    # never observe the new generation with old registry values
    generation += 1


def get_flags(flags) -> dict:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        key = k if k.startswith("FLAGS_") else "FLAGS_" + k
        out[k] = _REGISTRY[key]["value"]
    return out


def flag(name: str):
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _REGISTRY[key]["value"]


# Core flags (subset of reference's 187; grows as subsystems land).
define_flag("FLAGS_check_nan_inf", False, "scan every op output for nan/inf")
define_flag("FLAGS_use_compiled_eager", True, "jit-compile per-op eager dispatch")
define_flag("FLAGS_eager_cache_size", 4096, "per-op executable cache entries")
define_flag("FLAGS_eager_defer_vjp", True,
            "eager grad ops run a lean fwd-only executable; the vjp is "
            "re-derived inside one jitted backward call (trades ~1 extra "
            "fwd of the op's FLOPs in backward for ~2x cheaper per-op "
            "dispatch — see core/dispatch._build_entry). Operand "
            "retention: the deferred closure pins only the forward "
            "operands the vjp recompute provably reads (per-signature "
            "jaxpr liveness mask, computed on the first backward; until "
            "then one closure pins all operands — see _bwd_used_mask)")
define_flag("FLAGS_to_static_donate", True, "donate captured buffers in to_static")
define_flag("FLAGS_to_static_segmented", True,
            "on graph break, run segmented lazy execution (compiled XLA "
            "segments bridged eagerly) instead of whole-function eager")
define_flag("FLAGS_enable_double_grad", True,
            "record per-node re-derivation ctx for grad(create_graph=True); "
            "disable to shed the extra operand retention")
define_flag("FLAGS_log_level", 0, "VLOG-style verbosity")
define_flag("FLAGS_benchmark", False,
            "benchmark mode: block until each op's outputs are ready "
            "(per-op device sync, ≙ reference benchmark flag)")
define_flag("FLAGS_check_nan_inf_level", 0,
            "0: raise on nan/inf when FLAGS_check_nan_inf; >=1: warn only")
define_flag("FLAGS_cudnn_deterministic", False, "parity shim; XLA is deterministic")
define_flag("FLAGS_embedding_deterministic", False, "parity shim")
define_flag("FLAGS_allocator_strategy", "xla", "parity shim; XLA owns allocation")

# Reference flag-name parity (flags.cc defines 187 PHI_DEFINE_EXPORTED_*;
# the commonly consumed ones are registered here so set_flags/get_flags and
# FLAGS_* env seeding work for ported code — shims note where XLA makes the
# knob moot).
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "shim; XLA GC owns buffers")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92,
            "maps to XLA_PYTHON_CLIENT_MEM_FRACTION at init")
define_flag("FLAGS_gpu_memory_limit_mb", 0, "per-chip HBM cap shim")
define_flag("FLAGS_initial_cpu_memory_in_mb", 500, "host allocator shim")
define_flag("FLAGS_use_pinned_memory", True, "host staging shim")
define_flag("FLAGS_conv_workspace_size_limit", 512, "shim; XLA autotunes")
define_flag("FLAGS_cudnn_exhaustive_search", False, "shim; XLA autotunes")
define_flag("FLAGS_sync_nccl_allreduce", False,
            "shim; ICI collectives are compiler-scheduled")
define_flag("FLAGS_max_inplace_grad_add", 0, "grad accumulation fusion shim")
define_flag("FLAGS_apply_pass_to_program", False, "shim; XLA pass pipeline")
define_flag("FLAGS_new_executor_serial_run", False, "shim; XLA owns scheduling")
define_flag("FLAGS_use_stream_safe_cuda_allocator", True, "shim")
define_flag("FLAGS_call_stack_level", 1, "error stack verbosity (1|2|3)")
define_flag("FLAGS_enable_pir_api", True, "shim; jaxpr/StableHLO ARE the IR")
define_flag("FLAGS_use_cinn", True, "shim; XLA IS the tensor compiler")
define_flag("FLAGS_cinn_subgraph_graphviz_dir", "", "shim")
define_flag("FLAGS_low_precision_op_list", 0, "amp op-stats collection level")
define_flag("FLAGS_enable_auto_parallel_align_mode", False,
            "bitwise-align debugging shim")
define_flag("FLAGS_flash_attn_version", 2, "pallas flash kernel version")
define_flag("FLAGS_set_to_1d", False, "0-D tensor compat shim")
define_flag("FLAGS_tensor_operants_mode", "eager", "parity shim")
define_flag("FLAGS_jit_engine_type", "xla", "executor engine selector shim")
define_flag("FLAGS_allreduce_record_one_event", False, "comm stream shim")
define_flag("FLAGS_distributed_heartbeat_timeout", 600,
            "comm watchdog default timeout (seconds)")
define_flag("FLAGS_rpc_retry_times", 3, "rpc retry shim")
define_flag("FLAGS_dataloader_use_shared_memory", True,
            "native shm ring transport for DataLoader workers")
define_flag("FLAGS_enable_to_static", True,
            "global to_static toggle (jit.enable_to_static)")
define_flag("FLAGS_jit_code_level", 100, "SOT code-dump verbosity shim")
define_flag("FLAGS_jit_verbosity", 0, "dy2static logging verbosity shim")
define_flag("FLAGS_jit_log_to_stdout", False,
            "mirror dy2static logs to stdout (set_verbosity also_to_stdout)")
define_flag("FLAGS_flash_autotune", True,
            "runtime autotune of Pallas flash attention block sizes per "
            "shape family (≙ phi autotune/auto_tune_base.h)")
define_flag("FLAGS_flash_tune_bwd_split", True,
            "autotune backward (dq/dkv) flash block sizes separately from "
            "the forward's instead of reusing the forward winner")
define_flag("FLAGS_flce_chunk_axis", "auto",
            "fused_linear_cross_entropy chunk axis: vocab | tokens | auto "
            "(auto = vocab when a multiple-of-128 divisor exists, else "
            "tokens — tools/sweep_ce_chunk.py measures the ladder)")
define_flag("FLAGS_flce_token_chunk", 1024,
            "token-chunk size for the sequence-chunked fused CE path "
            "(tokens per [chunk, H] @ [H, V] GEMM; <= 0 disables)")
define_flag("FLAGS_dy2static", True,
            "to_static capture-time AST rewrite of tensor-predicate "
            "if/while/for into lax.cond/while_loop/scan "
            "(jit/dy2static); off = pre-dy2static behavior (any "
            "data-dependent control flow is a graph break)")
define_flag("FLAGS_dy2static_speculate", True,
            "during to_static discovery, abstractly trace the UNTAKEN "
            "branch of converted ifs so tensors it reads are recorded as "
            "captures instead of being baked as constants at trace time")
define_flag("FLAGS_jit_debug_program", False,
            "retain each to_static specialization's traceable closure so "
            "CompiledFunction.program_text() can print its jaxpr (pins "
            "the compile-call args; tests/tools only)")
define_flag("FLAGS_lazy_break_sites", True,
            "record the user file:line that forces each segmented-lazy "
            "flush (graph-break sites, tools/report_graph_breaks.py)")
define_flag("FLAGS_pallas_fused_ops", True,
            "route rms/layer norm (+fused residual add), rotary, SwiGLU "
            "and dropout+add through the Pallas fused kernels on TPU above "
            "the size threshold (ops/pallas_norm.py); off = the XLA "
            "compositions everywhere")
define_flag("FLAGS_analysis_vmem_limit_mb", 16,
            "per-core VMEM budget (MiB) the static analyzer checks Pallas "
            "launch configs against (analysis/vmem.py D5: flash autotune "
            "entries + norm block configs fail lint, not runtime)")
define_flag("FLAGS_analysis_fusion_min_elems", 4096,
            "fusion-miss detector (analysis D4) reporting floor: "
            "norm/rotary/swiglu/dropout-add compositions smaller than "
            "this many elements are not worth a finding")
define_flag("FLAGS_analysis_collective_min_bytes", 65536,
            "SPMD collective audit (analysis D10) warning floor: an "
            "all_gather whose output is consumed only by elementwise/"
            "slice ops fires the accidental-all-gather warning only at "
            "or above this per-device byte volume (smaller gathers stay "
            "attribution notes)")
define_flag("FLAGS_analysis_ici_gbps", 90.0,
            "per-link ICI bandwidth (GB/s) the static cost model "
            "(analysis/costmodel.py) charges collectives on intra-slice "
            "mesh axes against in its alpha-beta model")
define_flag("FLAGS_analysis_dcn_gbps", 12.5,
            "per-host DCN bandwidth (GB/s) for collectives on mesh axes "
            "a MeshConfig maps to the data-center network "
            "(MeshConfig.dcn_axes — the hybrid-mesh fabric split)")
define_flag("FLAGS_analysis_ici_alpha_us", 1.0,
            "per-hop ICI latency (microseconds) — the alpha term of the "
            "static cost model's alpha-beta collective estimate")
define_flag("FLAGS_analysis_dcn_alpha_us", 25.0,
            "per-hop DCN latency (microseconds) — the alpha term for "
            "collectives on dcn-mapped mesh axes")
define_flag("FLAGS_analysis_plan_regress_pct", 20.0,
            "D18 audit_plan threshold: the chosen MeshConfig predicted "
            "at least this percent slower than the best valid candidate "
            "in the same PlanReport is a lint warning")
define_flag("FLAGS_analysis_hbm_limit_mb", 0.0,
            "per-device HBM budget (MiB) for the static liveness pass: "
            "a candidate plan whose predicted peak exceeds it is "
            "rejected in autoplan.search and is a D18 error for the "
            "chosen config (0 = no budget check)")
define_flag("FLAGS_analysis_calibration_tol_pct", 10.0,
            "D19 audit_cost_model_calibration tie tolerance: a "
            "predicted-order pair only counts as a misprediction when "
            "the measured tok/s of the predicted-slower config beats "
            "the predicted-faster one by more than this percent "
            "(virtual-mesh walls are noisy; near-ties are not signal)")
define_flag("FLAGS_pallas_decode", True,
            "route paged decode attention through the Pallas flash-decode "
            "kernel (ops/pallas_decode.py) on TPU above the size "
            "threshold; off = the XLA gather+softmax composition "
            "everywhere")
define_flag("FLAGS_kv_block_size", 16,
            "tokens per KV-cache block in the paged serving engine "
            "(text/paged_cache.py); must be a multiple of 8 so a "
            "(block_size, head_dim) cache tile is sublane-aligned")
define_flag("FLAGS_kv_cache_dtype", "model",
            "paged KV cache storage dtype: model (match the model's "
            "compute dtype) | int8 (per-block-scale quantized cache — "
            "decode reads halve; blocks requantize on append)")
define_flag("FLAGS_serving_slots", 8,
            "slot count of the continuous-batching serving engine "
            "(inference/engine.py): the fixed request-slot array the "
            "per-step program runs over; requests join freed slots "
            "mid-flight")
define_flag("FLAGS_prefix_cache", True,
            "content-hash full KV blocks in the paged serving engine and "
            "serve shared prompt prefixes from cached blocks (zero "
            "prefill for those pages); finish releases blocks to an LRU "
            "of refcount-0 cached blocks instead of the free list, "
            "copy-on-write guards partially-overwritten shared blocks")
define_flag("FLAGS_chunked_prefill_tokens", 256,
            "split prompt prefill into chunks of at most this many "
            "tokens, one chunk per scheduler tick interleaved with "
            "decode — bounds the head-of-line TTFT/TPOT cost of a long "
            "prompt on in-flight decodes; 0 = monolithic prefill "
            "(cache-hit suffixes still ride one chunk program)")
define_flag("FLAGS_prefix_cache_max_blocks", 0,
            "cap on refcount-0 cached prefix blocks held in the LRU "
            "(0 = bounded only by pool pressure); eviction never touches "
            "a block a live request references")
define_flag("FLAGS_residual_dtype", "float32",
            "dtype of the transformer residual stream in text/models "
            "(float32 | bfloat16): bfloat16 keeps every inter-kernel "
            "activation crossing HBM in bf16 — f32 survives only inside "
            "the norm kernels' accumulation — halving the elementwise "
            "traffic on this bandwidth-capped device; loss drift is "
            "bounded by tests/test_pallas_norm.py")
define_flag("FLAGS_obs_metrics", False,
            "opt-in for obs registry instrumentation OUTSIDE the serving "
            "engine (hapi TelemetryCallback auto-attach in fit()); the "
            "serving engine always records into its own registry and the "
            "compile watchdog always records compile events — both are "
            "off the steady-state hot path")
define_flag("FLAGS_obs_log_path", "",
            "JSONL event log path (obs/metrics.py): compile events, "
            "logger records and registry snapshots append here as one "
            "structured line each; empty = disabled")
define_flag("FLAGS_obs_compile_storm_threshold", 8,
            "compile watchdog (obs/watchdog.py): more than this many "
            "DISTINCT program keys for one (site, family) is a "
            "recompile-storm warning in audit_recompiles — bucketing "
            "keeps real ladders O(log L), exact-length keying blows "
            "past it")
define_flag("FLAGS_ckpt_save_retries", 3,
            "checkpoint saves retry transient IO errors this many times "
            "with exponential backoff before surfacing "
            "CheckpointSaveError (ckpt/core.py); applies to sync and "
            "async saves alike")
define_flag("FLAGS_ckpt_retry_backoff_s", 0.05,
            "base of the checkpoint-save retry backoff: attempt k sleeps "
            "base * 2^k seconds")
define_flag("FLAGS_ckpt_async", True,
            "CheckpointCallback commits checkpoints on the background "
            "thread (the device->host copy stays synchronous, so the "
            "next step's donation can't race the bytes being written); "
            "off = every periodic save blocks the train loop")
define_flag("FLAGS_ckpt_max_in_flight", 2,
            "bound on queued async checkpoint saves; AsyncCheckpointer."
            "save() blocks (backpressure) when this many are already in "
            "flight instead of accumulating unbounded host copies")
define_flag("FLAGS_ckpt_keep_last_n", 0,
            "checkpoint retention: keep only the newest N committed "
            "checkpoints under a root (0 = keep all); the dir the "
            "`latest` pointer names is never deleted, deletion is "
            "strictly oldest-first and only touches fully-committed "
            "dirs (ckpt/core.py gc_checkpoints)")
define_flag("FLAGS_ckpt_stall_seconds", 300.0,
            "checkpoint-stall watchdog: a save whose wall time exceeds "
            "this becomes an obs.audit_ckpt_stalls warning finding "
            "(gated by the graft_lint ckpt smoke)")
define_flag("FLAGS_obs_http_port", 0,
            "when > 0 the ServingEngine exposes its metrics registry at "
            "http://127.0.0.1:<port>/metrics (Prometheus text "
            "exposition, stdlib http.server daemon thread); 0 = off")
define_flag("FLAGS_obs_log_max_mb", 64,
            "size cap in MB for the JSONL event log at FLAGS_obs_log_path "
            "(obs/metrics.py): past the cap the file rotates to "
            "<path>.1 .. <path>.N between records — a line is never torn "
            "mid-write; 0 = unbounded (the pre-round-14 behavior)")
define_flag("FLAGS_obs_log_backups", 3,
            "rolled JSONL event-log files kept after rotation "
            "(<path>.1 newest .. <path>.N oldest); the oldest is deleted "
            "when a rotation would exceed N")
define_flag("FLAGS_obs_flight_requests", 256,
            "per-engine flight-recorder ring capacity (obs/flight.py): "
            "finished request timelines kept for dump_trace(); the "
            "oldest finished flight is evicted past the cap — active "
            "requests are never evicted")
define_flag("FLAGS_obs_flight_dir", "",
            "anomaly auto-dump directory for the flight recorder: on a "
            "request timeout, a TTFT SLO breach "
            "(FLAGS_obs_slo_ttft_ms) or a post-warmup compile the "
            "engine writes a Chrome-trace JSON postmortem here "
            "(flight_<trigger>_<n>.json, capped per engine); empty = "
            "record but never auto-dump")
define_flag("FLAGS_obs_slo_ttft_ms", 0.0,
            "TTFT SLO in ms for the flight recorder's anomaly trigger: "
            "a request whose first token lands later than this "
            "auto-dumps the flight ring (FLAGS_obs_flight_dir) and "
            "counts serving_flight_dumps_total{trigger=slo_breach}; "
            "0 = no SLO trigger")
define_flag("FLAGS_obs_cost_capture", True,
            "capture XLA cost_analysis()/memory_analysis() (flops, bytes "
            "accessed, HBM footprint) into the compile event and the "
            "per-program cost ledger (obs/costs.py) at the AOT compile "
            "sites (serving buckets, generation engine; to_static under "
            "FLAGS_jit_debug_program) — compiled executables carry the "
            "analysis for free, no extra compile is paid")
define_flag("FLAGS_obs_peak_gbps", 0.0,
            "peak HBM bandwidth (GB/s) the roofline_utilization gauges "
            "divide achieved bytes/s by; 0 = the published peak of this "
            "device_kind (obs/peaks.py; an unknown device raises — "
            "off-chip runs set this flag)")
define_flag("FLAGS_obs_cost_regress_pct", 25.0,
            "analysis D8 (audit_cost_regressions) threshold: a compiled "
            "program whose bytes-accessed grew more than this percent "
            "over tools/cost_baseline.json fails lint like a dtype "
            "regression")
define_flag("FLAGS_obs_train_flight_steps", 64,
            "training flight-recorder ring capacity "
            "(obs/train_flight.py): finished per-step span timelines "
            "kept for dump_trace(); the oldest finished step is evicted "
            "past the cap — the active step never is")
define_flag("FLAGS_obs_data_wait_ms", 100.0,
            "data-starvation threshold for the training flight recorder "
            "and analysis D12: a step whose data_wait span (loader "
            "blocked before the batch arrived) exceeds this many ms "
            "counts a data_starvation anomaly and auto-dumps the step "
            "ring (FLAGS_obs_flight_dir); 0 = trigger off")
define_flag("FLAGS_obs_step_spike_factor", 3.0,
            "step-time-spike anomaly trigger: a train step whose wall "
            "exceeds this factor times the rolling median of recent "
            "steps (min population 8) auto-dumps the step ring; "
            "0 = trigger off")
define_flag("FLAGS_obs_peak_tflops", 0.0,
            "peak device compute (TFLOP/s, bf16) the train_mfu gauges "
            "divide achieved FLOP/s by; 0 = the published peak of this "
            "device_kind (obs/peaks.py; an unknown device raises — "
            "off-chip runs set this flag)")
define_flag("FLAGS_partitioner_heuristics", True,
            "declarative partitioner (distributed/partitioner): "
            "rule-match UNANNOTATED parameters by shape/name heuristics "
            "(2D up/down projections, embedding-shaped tables) instead "
            "of leaving them replicated; every guess is a named note in "
            "the PartitionPlan surfaced by the graft_lint spmd smoke")
define_flag("FLAGS_partitioner_sep_impl", "ring",
            "attention exchange for sep-axis (context-parallel) "
            "partitioner configs: ring (lax.ppermute K/V rotation, any "
            "head count) | ulysses (all-to-all seq<->head transpose, "
            "needs heads % sep == 0 — falls back to ring otherwise)")
define_flag("FLAGS_partitioner_fsdp_min_size", 1024,
            "parameters with fewer elements than this stay replicated "
            "instead of ZeRO-3 fsdp-sharded (tiny tensors pay the "
            "per-use all-gather latency without meaningful HBM savings)")
define_flag("FLAGS_spec_decode", "off",
            "speculative decoding on the paged serving engine "
            "(inference/speculative.py): off | ngram (model-free "
            "prompt-lookup proposer — the tail of prompt+generation is "
            "matched against earlier history and the continuation "
            "proposed) | draft (a small draft model proposes; pass it "
            "via SpecConfig(draft_model=...)). Proposed tokens are "
            "verified K+1 at a time in ONE batched paged-attention "
            "pass; greedy outputs stay token-identical to the "
            "non-speculative engine")
define_flag("FLAGS_spec_k", 4,
            "speculation depth: tokens proposed per verify window "
            "(each window scores K+1 candidate positions in one pass "
            "and emits 1..K+1 tokens depending on acceptance)")
define_flag("FLAGS_spec_min_accept", 0.1,
            "D16 audit_spec_decode acceptance floor: a WARMED engine "
            "whose overall speculative acceptance rate falls below "
            "this fraction is burning verify FLOPs for no goodput — "
            "lint warning (graft_lint `paged` smoke fire-fixture "
            "self-tests the detector)")
define_flag("FLAGS_router_policy", "prefix_affine",
            "placement policy of the multi-replica serving router "
            "(serving/router.py): prefix_affine (route by prompt "
            "fingerprint to the replica whose prefix cache already "
            "holds the blocks, falling back to least_loaded) | "
            "least_loaded (queue depth + free-block budget from "
            "stats()) | round_robin")
define_flag("FLAGS_router_fingerprint_blocks", 1024,
            "per-replica bound on the router's prefix fingerprint "
            "index: block hashes remembered per replica for "
            "prefix_affine placement (LRU beyond the cap; 0 disables "
            "fingerprint tracking and prefix_affine degrades to "
            "least_loaded)")
define_flag("FLAGS_router_sessions_max", 4096,
            "session-affinity map bound: session IDs the router pins "
            "to their replica (LRU beyond the cap — an evicted session "
            "re-pins via the placement policy on its next turn)")
define_flag("FLAGS_router_drain_ms", 10000.0,
            "default drain deadline for router.drain(): in-flight "
            "requests on the draining replica get at most this many "
            "ms to finish before the round-12 per-request deadline "
            "path timeout-finishes them (0 = wait forever)")
define_flag("FLAGS_router_skew_pct", 0.9,
            "D17 audit_fleet placement-skew threshold: one replica "
            "taking more than this fraction of routed requests while "
            "another ready replica got none is a lint warning "
            "(graft_lint `router` smoke self-tests the detector)")
define_flag("FLAGS_weight_only_dtype", "none",
            "default weight-only quantization of the serving engines' "
            "decode matmuls + lm_head (text/generation.py, "
            "inference/engine.py): none | int8 (per-channel scales, the "
            "round-5 1.67× bandwidth win) | int4 (true 2-nibbles-per-byte "
            "packed storage, ops/quantized.py — packed bytes are the only "
            "HBM weight traffic); per-call weight_quant= overrides")
define_flag("FLAGS_pallas_quant_matmul", True,
            "route int4 weight-only matmuls through the Pallas fused "
            "dequant-matmul kernel (ops/quantized.py: unpack + scale in "
            "VMEM) on TPU above the size threshold; off = the XLA "
            "take-bits composition everywhere (the parity oracle)")
define_flag("FLAGS_amp_fp8", False,
            "fp8 GEMM training leg of the amp policy (amp/fp8.py): the "
            "decoder-block projections (qkv/o/gate/up/down) run "
            "e4m3-forward / e5m2-gradient matmuls with delayed scaling — "
            "per-tensor amax history rings threaded as state through "
            "to_static, never host round-trips; loss parity vs bf16 is "
            "bounded by tests/test_quantized.py")
define_flag("FLAGS_fp8_amax_history", 16,
            "length of the per-tensor amax history ring delayed fp8 "
            "scaling maxes over (amp/fp8.py Fp8State)")
define_flag("FLAGS_debug_thread_checks", False,
            "owner-thread contract assertions on the deliberately "
            "single-threaded serving objects (ServingEngine, "
            "PagedKVCache's block pool, PrefixCache): a call from a "
            "thread other than the first user raises "
            "ConcurrencyContractError and records a D15 lint violation "
            "(core/lockdep.py ThreadContract). Debug mode — the "
            "graft_lint `conc` smoke and the thread-stress tests enable "
            "it; production leaves the checks compiled out to one flag "
            "lookup per engine call")


# the full reference flag surface (compat entries; must come after the
# real-behavior definitions above so those win)
from . import flags_compat as _flags_compat  # noqa: E402,F401
