#!/usr/bin/env python
"""graft_lint — program auditor + AST lint CLI (paddle_tpu.analysis).

Runs the tracer-safety AST lint over paddle_tpu/ source and, per model,
compiles the LLaMA/GPT/BERT smoke configs (forward AND a 2-step AdamW
train step, the same configs tools/report_graph_breaks.py smokes) with
FLAGS_jit_debug_program=1 and audits the captured jaxprs:

  D1 dtype-stream (bf16 policy violations / silent promotions)
  D2 donation (train-step buffers not updated in place, with byte cost)
  D3 host-sync (graph-break flush sites, eager fallbacks, host callbacks)
  D4 fusion-miss (unfused norm/rotary/swiglu/dropout-add/decode-attention
     + gating reason)
  D5 VMEM budget (flash autotune entries + norm/decode configs vs the
     per-core limit)

The special model name `paged` audits the SERVING step program instead: a
tiny-LLaMA 2-slot continuous-batching engine is run through real
prefill/decode steps and its decode program's jaxpr goes through the
fusion-miss/callback/dtype detectors plus the D5 decode-config budget at
default flags.

The special model name `obs` (round 11) smokes the telemetry contract: a
tiny engine runs a warmup pass, declares warmup done, serves steady-state
requests, and the gate fails if required serving metrics are missing or
the compile watchdog saw a post-warmup retrace / recompile storm
(obs/watchdog.py audit_recompiles). It also drives one checkpoint
save/restore cycle and requires the REQUIRED_CKPT_METRICS rows.
Round 14 extends it with the flight-recorder/cost contract: the warmed
engine must dump a VALID Chrome-trace/Perfetto JSON (per-request spans
tiling the TTFT decomposition), every decode bucket it drove must have
an analyzed obs cost-ledger row (XLA bytes/flops + measured walls), and
analysis D8 (audit_cost_regressions) gates per-program bytes-accessed
against the committed tools/cost_baseline.json. Round 16 adds the
TRAINING contract: a short instrumented Model.fit must dump a valid
training trace (each step's data_wait+compute spans tile the recorded
step wall), land every REQUIRED_TRAIN_METRICS row (train_mfu, goodput,
data-wait), and pass analysis D12 (audit_train_steps: starvation
streaks / MFU collapse) at default flags.

The special model name `ckpt` (round 12) smokes crash consistency
end-to-end: a tiny model + AdamW trains, checkpoints twice, the NEWEST
checkpoint gets a bit flipped, and restore must fall back to the last
good one with a named reason and bit-exact state — plus the checkpoint
stall/failure audit (obs.audit_ckpt_stalls).

The special model name `spmd` (round 15) audits the SHARDED surface: the
tp x dp hybrid train step (the same shape __graft_entry__.dryrun_multichip
phase A proves) compiles on the 8-device virtual CPU mesh with
FLAGS_jit_debug_program=1 and runs through the full detector suite
INCLUDING the SPMD trio — D9 sharding coverage (every non-trivial mesh
axis must appear on a stream-size tensor's sharding), D10 collective
audit (jaxpr-level collectives attributed to axes with byte volume;
accidental all-gathers warn), D11 in-program device_put. The smoke then
SELF-TESTS the fire fixtures: a deliberately unsharded stream tensor, a
gratuitous all-gather, and an in-program device_put must each produce an
unsuppressed warning — a detector that stopped firing fails the gate
exactly like a detector that started firing falsely. To give the spmd
smoke its mesh, the CLI forces the same virtual 8-device CPU platform
tests/conftest.py uses, for every smoke. Round 18 adds the DECLARATIVE
half: the partitioner (distributed/partitioner) must shard the
UNMODIFIED tiny-LLaMA train step from one data+fsdp+tp MeshConfig with
clean D1-D11 + full D9 coverage, and an all-replicated rule table must
still fire D9 through the partitioner path (silently-dead self-test).

The special model name `conc` (round 17) smokes the CONCURRENCY
contract: a genuinely multi-threaded serving/ckpt/obs stress (engine
ticks + concurrent /metrics scrapes + overlapped async checkpoint
commits + a comm-watchdog scan) runs with core/lockdep recording on and
FLAGS_debug_thread_checks enabled; the D14 audit requires the recorded
lock-ORDER graph to be acyclic with zero blocking-calls-under-hot-lock,
D15 requires zero owner-thread contract violations, and the D13/D14/D15
fire fixtures then self-test (tests/lint_fixtures/fx_conc_*.py + a
deterministic two-lock cycle + a cross-thread contract breach) — a
silently-dead detector fails the gate. The D13 lock-discipline AST lint
itself (guarded-by / shared-state) rides EVERY run's AST pass.

The special model name `router` (round 20) smokes the MULTI-REPLICA
serving fabric: a real 2-replica tiny-LLaMA fleet behind
paddle_tpu.serving.Router with owner-thread contracts enforced — the
prefix_affine policy must concentrate a shared-prefix stream (≥1 router
affinity hit, ≥1 fleet prefix-cache hit), a drain/handoff rolling
restart mid-stream must complete every future exactly once (replacement
admitted only after warmup + readiness), zero compiles may land after
any replica's warmup barrier, D17 audit_fleet must come back clean, the
REQUIRED_FLEET_METRICS rows must exist in the router registry, and the
D17 affinity-defeat fire fixture (a drifting fingerprint scattering
byte-identical prompts) must still trip its warning.

The special model name `quant` (round 20) smokes the QUANTIZATION
byte-budget claims: int8/int4 weight-only paged engines plus an int4-KV
engine drive the same stream as a full-precision twin; D20
audit_quantized_bytes must verify the live decode-program pairs' ledger
boundary bytes against the 1.8x/3.4x shrink budgets, D20b
audit_silent_dequant + D1/D4 must be clean on the quantized decode
jaxprs, zero compiles may land after any engine's warmup barrier, and
the D20/D20b fire fixtures (a non-shrinking ledger pair, a weight-sized
int8->f32 convert) must trip — silence fails the gate.

The special model name `plan` (round 21) smokes the STATIC COST MODEL:
`autoplan.search` must rank ≥6 valid MeshConfigs for tiny-LLaMA on the
8-device virtual mesh from one abstract lowering (nothing executes),
D18 audit_plan must be clean on the search's own top-1, D19
audit_cost_model_calibration gates the predicted ordering against
MEASURED tok/s of the three partitioner_scaling configs, and the D18
(worst-candidate deploy + rigged HBM budget) and D19 (rigged-fabric
ranking flip) fire fixtures must trip — silence fails the gate.

Exit code: 0 when no unsuppressed warning/error finding survives the
baseline (notes never fail); 1 otherwise. CI runs
`graft_lint.py --models llama,gpt,bert,paged,obs,ckpt,spmd,conc,router,plan,quant --json`
via tools/check_scoreboard — round 17 splits that into PARALLEL
subprocess groups (check_scoreboard.LINT_GROUPS) so the gate wall stays
at the slowest group; each worker passes `--defer-stale` and the gate
aggregates baseline match counts across the union. Baseline entries
that matched ZERO findings are reported as `stale-suppression` (warning
on a full-coverage run, note on a partial one); `--prune-baseline`
rewrites the baseline without them.

Usage:
    python tools/graft_lint.py                      # AST lint + D5 only
    python tools/graft_lint.py --models llama,gpt,bert,paged,spmd
    python tools/graft_lint.py --json               # machine output
    python tools/graft_lint.py --baseline my.json   # suppression file
    python tools/graft_lint.py --no-ast             # jaxpr audits only
    python tools/graft_lint.py --models llama,gpt,bert,paged,obs,ckpt,spmd,conc \
        --prune-baseline                            # drop stale suppressions

Baseline format: see paddle_tpu/analysis/findings.py (default file
tools/lint_baseline.json; suppressed findings stay visible in --json).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")

#: the full CI smoke set (check_scoreboard.lint_gate's default): staleness
#: of baseline entries is only a gate FAILURE when a run covers all of it
#: — a partial run legitimately leaves model-specific suppressions
#: unmatched
CI_MODELS = ("llama", "gpt", "bert", "paged", "obs", "ckpt", "spmd",
             "conc", "router", "plan", "quant")

#: one tiny-LLaMA shared by the serving-side smokes (`paged`, `obs`): the
#: engines key their AOT executables on spec + param AVALS, so a shared
#: instance guarantees every engine in the run rides the round-14
#: executable cache instead of warming its own programs
_TINY_MODEL = None


def _tiny_llama():
    global _TINY_MODEL
    if _TINY_MODEL is None:
        import paddle_tpu as paddle
        from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4,
                          max_position_embeddings=64)
        _TINY_MODEL = LlamaForCausalLM(cfg)
        _TINY_MODEL.eval()
    return _TINY_MODEL


def audit_model(name: str) -> list:
    """Compile the named smoke config (forward + train step) and run every
    program-level detector. Imports stay inside so `--no-models` runs need
    no jax session."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from report_graph_breaks import SMOKES

    fwd_fn, args = SMOKES[name]()
    model = fwd_fn.__self__
    findings = []

    paddle.set_flags({"FLAGS_jit_debug_program": True})
    try:
        sfwd = paddle.jit.to_static(fwd_fn)
        for _ in range(3):
            sfwd(*args)
        findings += analysis.audit_compiled(sfwd, loc=f"{name}/forward")

        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        @paddle.jit.to_static
        def train_step(*a):
            loss = fwd_fn(*a)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        for _ in range(4):
            loss = train_step(*args)
        assert np.isfinite(float(loss)), f"{name} train step diverged"
        findings += analysis.audit_compiled(train_step,
                                            loc=f"{name}/train_step")

        # D5 at this model's width (bf16 itemsize: the flagship stream)
        cfg = getattr(model, "config", None)
        hidden = getattr(cfg, "hidden_size", None)
        if hidden:
            findings += analysis.audit_norm_config(
                hidden, itemsize=2, loc=f"{name}/norm-config")
    finally:
        paddle.set_flags({"FLAGS_jit_debug_program": False})
    return findings


def audit_serving() -> list:
    """The `paged` smoke: drive a tiny-LLaMA 2-slot serving engine through
    real prefill + decode steps (mixed-length requests, so a slot frees
    and refills), then audit the decode step program's jaxpr and the
    decode kernel's launch-config/pool budget at default flags.

    Round 13 extends the smoke with a SHARED-PREFIX stream: after a
    warmup request computes a multi-block prompt (and a second request
    warms the cache-hit chunk program), the engine declares warmup done
    and serves another request sharing the same prefix — the gate then
    requires (a) at least one prefix-cache block hit (D7: an
    identical-prefix stream that never hits means the cache is
    defeated), and (b) ZERO compiles after the warmup barrier (the
    cache-hit suffix path must ride already-compiled chunk programs)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis, obs
    from paddle_tpu.core.flags import flag
    from paddle_tpu.inference.engine import ServingEngine

    paddle.seed(0)
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=2)
    rs = np.random.RandomState(0)
    for ln, nt in ((3, 2), (6, 5), (4, 3)):
        eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
    out = eng.run()
    assert len(out) == 3 and all(len(v) for v in out.values()), \
        "paged smoke engine failed to drain"
    jx = eng.decode_program_jaxpr()
    findings = analysis.audit_fusion_misses(jx, loc="paged/decode_step")
    findings += analysis.audit_callbacks(jx, loc="paged/decode_step")
    findings += analysis.audit_dtype_stream(
        jx, policy=str(flag("FLAGS_residual_dtype")),
        loc="paged/decode_step")
    findings += analysis.audit_decode_config(
        eng.spec.head_dim, eng.block_size,
        group=max(1, eng.spec.num_heads // eng.spec.num_kv_heads),
        itemsize=2, kv_heads=eng.spec.num_kv_heads,
        pool_blocks=eng.allocator.num_blocks,
        slots=eng.max_slots, seq_pages=eng.pages,
        cached_blocks=eng.prefix_cache.cached_blocks,
        loc="paged/decode-config")

    # ---- shared-prefix stream (round 13): hit + zero-post-warmup gate
    obs.clear_events()
    eng2 = ServingEngine(model, max_slots=2)
    shared = rs.randint(0, 128, (2 * eng2.block_size + 1,))
    tail = rs.randint(0, 128, (3, 2))
    # request 1 computes + registers the prefix; request 2 warms the
    # cache-hit suffix chunk program at the buckets request 3 reuses
    eng2.add_request(np.concatenate([shared, tail[0]]), max_new_tokens=2)
    eng2.run()
    eng2.add_request(np.concatenate([shared, tail[1]]), max_new_tokens=2)
    eng2.run()
    eng2.finish_warmup()
    eng2.add_request(np.concatenate([shared, tail[2]]), max_new_tokens=2)
    out2 = eng2.run()
    assert len(out2) == 3, "shared-prefix smoke failed to drain"
    hits = int(eng2.prefix_cache.hits)
    if hits < 1:
        findings.append(analysis.Finding(
            "prefix-cache", "error", "paged/shared-prefix-smoke",
            "a 3-request stream sharing a 2-block prompt prefix produced "
            "ZERO prefix-cache hits at default flags — block reuse is "
            "not happening", data={"hits": hits}))
    else:
        findings.append(analysis.Finding(
            "prefix-cache", "note", "paged/shared-prefix-smoke",
            f"shared-prefix stream served {hits} block(s) from cache"))
    findings += analysis.audit_prefix_cache(
        eng2, loc="paged/shared-prefix-smoke")
    evs = [e for e in obs.compile_events() if e.site.startswith("serving")]
    findings += obs.audit_recompiles(evs, loc="paged/shared-prefix-smoke")

    # ---- speculative decode smoke (round 16): a 2-slot n-gram
    # speculating engine warms every program the steady stream rides
    # (spec-verify at buckets 1 and 2, plain decode for the mixed tick
    # and the empty-proposal fallback), declares warmup done, then
    # serves a repetitive-prompt request for ≥8 verify windows. Gates:
    # (a) ZERO post-warmup compiles on the verify family, (b) the
    # flight trace validates with verify-window spans covering the
    # steady run, (c) D4-family audits are clean on the verify
    # program's jaxpr, (d) the D16 greedy parity oracle vs a
    # non-speculative A/B engine on the same prompt.
    import tempfile

    from paddle_tpu.inference.speculative import AlwaysRejectProposer, \
        SpecConfig

    obs.clear_events()
    eng3 = ServingEngine(model, max_slots=2, spec_decode="ngram")
    base = np.tile(rs.randint(0, 128, (4,)), 5)     # repetitive stream
    eng3.add_request(base, max_new_tokens=6)        # spec bucket 1
    eng3.run()
    eng3.add_request(np.roll(base, 2), max_new_tokens=6)
    eng3.add_request(base, max_new_tokens=6, speculative=False)
    eng3.run()                                      # mixed spec/plain tick
    eng3.add_request(base, max_new_tokens=6)
    eng3.add_request(np.roll(base, 2), max_new_tokens=6)
    eng3.run()                                      # spec bucket 2
    eng3.finish_warmup()
    rid_s = eng3.add_request(base, max_new_tokens=24)
    out3 = eng3.run()
    # how often a random tiny model repeats itself is the draw's business:
    # two more repetitive prompts keep the window count off that edge
    for shift in (1, 3):
        eng3.add_request(np.roll(base, shift), max_new_tokens=24)
        eng3.run()
    eng_ab = ServingEngine(model, max_slots=2)
    rid_b = eng_ab.add_request(base, max_new_tokens=24)
    out_ab = eng_ab.run()
    parity = bool(np.array_equal(out3[rid_s], out_ab[rid_b]))
    findings += analysis.audit_spec_decode(
        eng3, parity=parity, loc="paged/spec-smoke")
    evs = [e for e in obs.compile_events() if e.site.startswith("serving")]
    findings += obs.audit_recompiles(evs, loc="paged/spec-smoke")

    fd, tpath = tempfile.mkstemp(prefix="graft_lint_spec_trace_",
                                 suffix=".json")
    os.close(fd)
    try:
        eng3.dump_trace(tpath)
        summary = obs.validate_trace(tpath)
        if summary["verify_spans"] < 8:
            findings.append(analysis.Finding(
                "spec-decode", "error", "paged/spec-smoke",
                "speculative smoke recorded fewer than 8 verify-window "
                "spans — the engine is not actually speculating tick "
                "over tick", data=dict(summary)))
    except (AssertionError, ValueError) as e:
        findings.append(analysis.Finding(
            "spec-decode", "error", "paged/spec-smoke",
            f"speculative trace dump failed validation: {e}"))
    finally:
        os.unlink(tpath)

    jxv = eng3.verify_program_jaxpr()
    findings += analysis.audit_fusion_misses(jxv, loc="paged/spec_verify")
    findings += analysis.audit_callbacks(jxv, loc="paged/spec_verify")
    findings += analysis.audit_dtype_stream(
        jxv, policy=str(flag("FLAGS_residual_dtype")),
        loc="paged/spec_verify")

    # ---- D16 fire-fixture self-test: a proposer that NEVER matches the
    # target must trip the acceptance-collapse warning on a warmed
    # engine. The warning is consumed here (it is the fixture working,
    # not a defect); a detector that stays silent is itself the gate
    # failure.
    eng4 = ServingEngine(
        model, max_slots=2,
        spec_decode=SpecConfig(proposer=AlwaysRejectProposer(4)))
    eng4.add_request(base, max_new_tokens=6)
    eng4.run()
    eng4.finish_warmup()
    eng4.add_request(np.roll(base, 1), max_new_tokens=6)
    eng4.run()
    fire = analysis.audit_spec_decode(eng4, loc="paged/spec-fire-fixture")
    if any(f.detector == "spec-decode" and f.severity == "warning"
           for f in fire):
        findings.append(analysis.Finding(
            "spec-decode", "note", "paged/spec-fire-fixture",
            "D16 fire fixture verified: the always-reject proposer "
            "tripped the acceptance-collapse warning",
            data={"accept_rate": eng4.spec_stats()["accept_rate"]}))
    else:
        findings.append(analysis.Finding(
            "spec-decode", "error", "paged/spec-fire-fixture",
            "D16 detector is SILENTLY DEAD: a warmed engine driven by "
            "an always-reject proposer produced no acceptance-collapse "
            "warning", data={"findings": [f.to_dict() for f in fire]}))
    return findings


#: metric names the obs smoke requires the serving registry to carry —
#: the instrumentation contract a refactor must not silently drop
REQUIRED_SERVING_METRICS = (
    "serving_ttft_seconds", "serving_queue_wait_seconds",
    "serving_prefill_seconds", "serving_decode_step_seconds",
    "serving_tpot_seconds", "serving_decode_tokens_total",
    "serving_prefill_tokens_total", "serving_requests_completed_total",
    "serving_requests_timeout_total",
    "serving_admission_rejects_total", "serving_admission_blocked_total",
    "serving_queue_depth", "serving_active_slots",
    "serving_block_pool_free_blocks", "serving_block_pool_used_blocks",
    # round 13: prefix cache + chunked prefill instrumentation
    "serving_prefix_blocks_hit_total", "serving_prefix_blocks_missed_total",
    "serving_prefill_chunks_total", "serving_prefix_cache_blocks",
    "serving_prefix_cache_referenced_blocks",
    "serving_prefix_cache_evictions_total",
    # round 14: flight recorder
    "serving_flight_anomalies_total", "serving_flight_dumps_total",
    "serving_flight_requests",
    # round 20: drain/handoff (router rolling restarts; zero on an
    # engine that never drained, so NOT in MUST_COUNT)
    "serving_drained_requests_total",
    # round 16: speculative decoding (NOT in MUST_COUNT — a non-spec
    # stream legitimately leaves them at zero)
    "serving_spec_windows_total", "serving_spec_proposed_tokens_total",
    "serving_spec_accepted_tokens_total", "serving_spec_accept_rate",
    "serving_spec_accepted_per_window",
    # PR 31: expert layers and the two-kind cache (NOT in MUST_COUNT — a
    # dense model with one kind of layer state never moves them)
    "serving_moe_local_picks_total", "serving_moe_routed_tokens_total",
    "serving_kv_window_bytes_held", "serving_kv_full_blocks_used",
    # PR 37: the latent (MLA) cache (NOT in MUST_COUNT — a model that
    # caches K and V never moves them)
    "serving_kv_latent_bytes_held", "serving_latent_ctx_tokens_total",
    # PR 38: the latent chunk kernel (zero off the chip and on any model
    # whose chunk attention is a composition)
    "serving_latent_chunk_kernel_blocks_total",
    # PR 40: recurrent state a slot (zero on a model without linear layers)
    "serving_state_slot_steps_total", "serving_state_tokens_total",
    "serving_recurrent_state_bytes_held",
    # held experts with a live pick (zero on a dense model)
    "serving_moe_experts_read_total")

#: process-default-registry rows the README "process-default registry"
#: catalog names (compile watchdog + cost attribution). The meta-test in
#: tests/test_flight.py pins README catalog rows to the REQUIRED_* sets;
#: post_warmup_compiles_total only materializes on an anomaly, so the
#: obs smoke's existence check uses the MUST_EXIST subset below.
REQUIRED_DEFAULT_METRICS = (
    "compiles_total", "compile_seconds", "post_warmup_compiles_total",
    "roofline_utilization")

MUST_EXIST_DEFAULT_METRICS = (
    "compiles_total", "compile_seconds", "roofline_utilization")

#: committed analysis-D8 baseline (per-program bytes-accessed from the
#: obs smoke's tiny serving engine)
COST_BASELINE = os.path.join(REPO, "tools", "cost_baseline.json")

#: checkpoint metric rows the obs smoke requires in the DEFAULT registry
#: after one save/restore cycle (the round-12 fault-tolerance contract)
REQUIRED_CKPT_METRICS = (
    "ckpt_save_seconds", "ckpt_restore_seconds", "ckpt_saves_total",
    "ckpt_restores_total", "ckpt_bytes_written_total", "ckpt_last_step")

#: training telemetry rows the obs smoke requires in the DEFAULT registry
#: after a short instrumented Model.fit (the round-16 training
#: flight-recorder / MFU / goodput contract)
REQUIRED_TRAIN_METRICS = (
    "train_step_seconds", "train_steps_total", "train_loss",
    "train_tokens_per_sec", "train_lazy_flushes_total",
    "train_data_wait_seconds", "train_mfu", "train_achieved_flops",
    "train_goodput_ratio", "train_goodput_seconds_total",
    "train_flight_steps", "train_flight_anomalies_total",
    "train_flight_dumps_total")

#: the subset that MUST have observed/counted after the smoke's drained
#: runs (rejects/blocked legitimately stay zero on a healthy stream)
MUST_COUNT_SERVING_METRICS = (
    "serving_ttft_seconds", "serving_queue_wait_seconds",
    "serving_prefill_seconds", "serving_decode_step_seconds",
    "serving_tpot_seconds", "serving_decode_tokens_total",
    "serving_prefill_tokens_total", "serving_requests_completed_total")

#: fleet telemetry rows the `router` smoke requires in the Router's
#: registry (round 20) — the multi-replica placement/failover contract;
#: tests/test_flight.py pins the README catalog rows to this set too
REQUIRED_FLEET_METRICS = (
    "router_requests_total", "router_prefix_affinity_hits_total",
    "router_session_affinity_hits_total", "router_rerouted_requests_total",
    "router_dead_replica_routes_total", "router_drains_total",
    "router_ready_replicas", "router_dead_replicas")


def audit_obs() -> list:
    """The `obs` smoke (round 11): drive a tiny-LLaMA 2-slot engine
    through a warmup pass, declare warmup done, run a steady-state
    request at the SAME buckets, then (a) assert the required serving
    metrics exist and counted, and (b) run the compile watchdog's
    recompile audit over the serving/generate event window — a
    post-warmup retrace or a storm fails the gate like a dtype
    regression."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis, obs
    from paddle_tpu.inference.engine import ServingEngine

    paddle.seed(0)
    obs.clear_events()
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=2)
    rs = np.random.RandomState(0)
    for ln, nt in ((3, 3), (6, 4), (4, 3)):     # warm both slot buckets
        eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
    eng.run()
    eng.finish_warmup()
    for ln, nt in ((5, 3), (3, 4)):             # steady state: same buckets
        eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
    out = eng.run()
    assert out, "obs smoke engine failed to drain"

    findings = []
    snap = eng.metrics()
    missing = [m for m in REQUIRED_SERVING_METRICS if m not in snap]
    zero = [m for m in MUST_COUNT_SERVING_METRICS
            if m not in missing
            and not any(s.get("count") or s.get("value")
                        for s in snap[m]["samples"])]
    if missing or zero:
        findings.append(analysis.Finding(
            "obs-coverage", "error", "obs/serving-smoke",
            f"serving registry lost required metrics — missing: {missing}, "
            f"never-observed: {zero}",
            data={"missing": missing, "zero": zero}))
    else:
        findings.append(analysis.Finding(
            "obs-coverage", "note", "obs/serving-smoke",
            f"{len(REQUIRED_SERVING_METRICS)} required serving metrics "
            "present and counting"))
    evs = [e for e in obs.compile_events()
           if e.site.startswith("serving") or e.site == "generate"]
    findings += obs.audit_recompiles(evs, loc="obs/serving-smoke")

    # ---- flight recorder + cost attribution (round 14): the warmed run
    # must dump a VALID Perfetto trace (per-request spans tiling TTFT)
    # and every decode bucket it drove must have an ANALYZED cost-ledger
    # row (XLA bytes/flops) with measured execution walls; D8 then gates
    # those bytes against the committed baseline.
    import tempfile

    from paddle_tpu.obs import costs as obs_costs

    fd, tpath = tempfile.mkstemp(prefix="graft_lint_trace_",
                                 suffix=".json")
    os.close(fd)
    summary = None
    try:
        eng.dump_trace(tpath)
        summary = obs.validate_trace(tpath)
    except (AssertionError, ValueError) as e:
        findings.append(analysis.Finding(
            "obs-flight", "error", "obs/flight-smoke",
            f"serving trace dump failed validation: {e}"))
    finally:
        os.unlink(tpath)
    if summary is not None:
        done = len(eng.completed)
        if summary["tiled_requests"] < done or not summary["events"]:
            findings.append(analysis.Finding(
                "obs-flight", "error", "obs/flight-smoke",
                f"trace dump degraded: {summary['tiled_requests']} "
                f"TTFT-tiled request timelines for {done} completed "
                f"requests ({summary['events']} events)",
                data=summary))
        else:
            findings.append(analysis.Finding(
                "obs-flight", "note", "obs/flight-smoke",
                f"trace dump valid: {summary['events']} events, "
                f"{summary['tiled_requests']}/{done} requests TTFT-tiled",
                data=summary))
    driven = [e for e in obs_costs.ledger("serving.decode")
              if e.exec_count > 0]
    unanalyzed = [e.program for e in driven if not e.analyzed]
    if not driven or unanalyzed:
        findings.append(analysis.Finding(
            "obs-cost", "error", "obs/cost-smoke",
            "cost ledger lost decode coverage — "
            + (f"no measured serving.decode programs" if not driven else
               f"programs without XLA cost analysis: {unanalyzed}"),
            data={"driven": [e.program for e in driven],
                  "unanalyzed": unanalyzed}))
    else:
        findings.append(analysis.Finding(
            "obs-cost", "note", "obs/cost-smoke",
            f"{len(driven)} decode program(s) carry XLA costs + measured "
            f"walls (buckets {sorted(e.bucket for e in driven)})"))
    snap_def = obs.default_registry().to_dict()
    missing_def = [m for m in MUST_EXIST_DEFAULT_METRICS
                   if m not in snap_def]
    if missing_def:
        findings.append(analysis.Finding(
            "obs-coverage", "error", "obs/default-registry",
            f"default registry lost required metrics: {missing_def}",
            data={"missing": missing_def}))
    if not os.path.exists(COST_BASELINE):
        findings.append(analysis.Finding(
            "cost-regression", "error", "obs/cost-smoke",
            "tools/cost_baseline.json is missing — D8 cannot gate; "
            "regenerate with tools/roofline_report.py --write-baseline"))
    else:
        findings += analysis.audit_cost_regressions(
            COST_BASELINE, loc="obs/cost-smoke")

    # the ckpt row (round 12): one save/restore cycle must land every
    # REQUIRED_CKPT_METRICS entry in the default registry
    import shutil
    import tempfile

    from paddle_tpu import ckpt

    root = tempfile.mkdtemp(prefix="graft_lint_obs_ckpt_")
    try:
        ckpt.save_checkpoint(root, 1, {"w": np.ones(8, np.float32)})
        ckpt.restore_checkpoint(root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    snap = obs.default_registry().to_dict()
    missing_ckpt = [m for m in REQUIRED_CKPT_METRICS if m not in snap]
    if missing_ckpt:
        findings.append(analysis.Finding(
            "obs-coverage", "error", "obs/ckpt-smoke",
            f"default registry lost required checkpoint metrics after a "
            f"save/restore cycle — missing: {missing_ckpt}",
            data={"missing": missing_ckpt}))
    else:
        findings.append(analysis.Finding(
            "obs-coverage", "note", "obs/ckpt-smoke",
            f"{len(REQUIRED_CKPT_METRICS)} required ckpt metrics present"))
    findings += audit_train_smoke()
    return findings


def audit_train_smoke() -> list:
    """The training half of the `obs` smoke (round 16): run a short
    instrumented Model.fit (TelemetryCallback with its flight recorder +
    goodput ledger on the DEFAULT registry), then require (a) a VALID
    training trace dump — every step's data_wait+compute spans tile the
    recorded step wall, re-checked by obs.validate_trace, (b) the
    REQUIRED_TRAIN_METRICS rows (MFU, goodput, data-wait among them),
    and (c) a clean analysis D12 (audit_train_steps) at default flags —
    a starvation streak or MFU collapse in the smoke's in-memory loader
    would mean the detector itself is miscalibrated."""
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis, obs

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 32), paddle.nn.ReLU(),
                               paddle.nn.Linear(32, 4))
    model = paddle.hapi.Model(net)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    model.prepare(opt, paddle.nn.MSELoss())
    rs = np.random.RandomState(0)
    data = [(rs.randn(8).astype("float32"), rs.randn(4).astype("float32"))
            for _ in range(16)]
    # eager steps have no compiled program to read flops from — declare
    # them (2 * params * 3 for fwd+bwd is the usual hand estimate; the
    # exact number only scales the MFU gauge, the smoke checks presence)
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    cb = paddle.hapi.TelemetryCallback(batch_tokens=8 * 4,
                                       step_flops=6.0 * n_params * 4)
    model.fit(data, batch_size=4, epochs=2, verbose=0, callbacks=[cb])

    findings = []
    steps_run = int(cb.ledger.steps)
    fd, tpath = tempfile.mkstemp(prefix="graft_lint_train_trace_",
                                 suffix=".json")
    os.close(fd)
    summary = None
    try:
        cb.flight.dump_trace(tpath)
        summary = obs.validate_trace(tpath)
    except (AssertionError, ValueError) as e:
        findings.append(analysis.Finding(
            "obs-train-flight", "error", "obs/train-smoke",
            f"training trace dump failed validation: {e}"))
    finally:
        os.unlink(tpath)
    if summary is not None:
        if summary["tiled_steps"] < steps_run or not summary["events"]:
            findings.append(analysis.Finding(
                "obs-train-flight", "error", "obs/train-smoke",
                f"training trace degraded: {summary['tiled_steps']} "
                f"wall-tiled step timelines for {steps_run} steps run "
                f"({summary['events']} events)", data=summary))
        else:
            findings.append(analysis.Finding(
                "obs-train-flight", "note", "obs/train-smoke",
                f"training trace valid: {summary['events']} events, "
                f"{summary['tiled_steps']}/{steps_run} steps tile their "
                "recorded walls", data=summary))
    snap = obs.default_registry().to_dict()
    missing = [m for m in REQUIRED_TRAIN_METRICS if m not in snap]
    zero = []
    for m in ("train_step_seconds", "train_steps_total", "train_mfu",
              "train_goodput_seconds_total", "train_data_wait_seconds"):
        if m not in missing and not any(
                s.get("count") or s.get("value")
                for s in snap[m]["samples"]):
            zero.append(m)
    if missing or zero:
        findings.append(analysis.Finding(
            "obs-coverage", "error", "obs/train-smoke",
            f"default registry lost required training metrics after an "
            f"instrumented fit — missing: {missing}, never-observed: "
            f"{zero}", data={"missing": missing, "zero": zero}))
    else:
        findings.append(analysis.Finding(
            "obs-coverage", "note", "obs/train-smoke",
            f"{len(REQUIRED_TRAIN_METRICS)} required training metrics "
            "present and counting"))
    findings += analysis.audit_train_steps(recorder=cb.flight,
                                           ledger=cb.ledger,
                                           loc="obs/train-smoke")
    return findings


def audit_ckpt() -> list:
    """The `ckpt` smoke (round 12): save → corrupt → restore-last-good on
    a tiny model, entirely through the public subsystem.  Proves in CI
    that (a) two committed checkpoints restore bit-exact, (b) a
    bit-flipped shard in the NEWEST one is caught by checksum
    verification and restore falls back to the previous good checkpoint
    with a named reason, and (c) the save window is stall/failure-free
    (obs.audit_ckpt_stalls)."""
    import shutil
    import sys as _sys
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis, ckpt, obs

    _sys.path.insert(0, os.path.join(REPO, "tests"))
    import faultinject as fi

    paddle.seed(0)
    np.random.seed(0)
    obs.clear_events()
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 8).astype("float32"))
    findings = []
    root = tempfile.mkdtemp(prefix="graft_lint_ckpt_")
    try:
        for step in (1, 2):
            loss = (model(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            if step == 1:
                ckpt.save_checkpoint(
                    root, 1, ckpt.capture_train_state(model, opt, step=1))
                good = {k: v.numpy().copy()
                        for k, v in model.state_dict().items()}
        with fi.bit_flip_shard(0, byte_offset=3):
            ckpt.save_checkpoint(
                root, 2, ckpt.capture_train_state(model, opt, step=2))
        r = ckpt.restore_checkpoint(root)
        ok = (r.step == 1
              and r.fallbacks
              and r.fallbacks[0]["reason"] == "checksum_mismatch"
              and all(np.array_equal(r.tree["model"][k], good[k])
                      for k in good))
        if ok:
            findings.append(analysis.Finding(
                "ckpt-smoke", "note", "ckpt/save-corrupt-restore",
                "bit-flipped newest checkpoint detected "
                "(checksum_mismatch); restore fell back to the last good "
                "checkpoint bit-exact"))
        else:
            findings.append(analysis.Finding(
                "ckpt-smoke", "error", "ckpt/save-corrupt-restore",
                f"restore-last-good contract violated: step={r.step}, "
                f"fallbacks={r.fallbacks}",
                data={"step": r.step, "fallbacks": r.fallbacks}))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    findings += obs.audit_ckpt_stalls(loc="ckpt/save-window")
    return findings


def audit_spmd() -> list:
    """The `spmd` smoke (round 15): compile the tp x dp hybrid train step
    (phase A of __graft_entry__.dryrun_multichip — fleet GSPMD sharding,
    tensor+sequence parallel tiny-LLaMA) on the 8-device virtual mesh and
    run the FULL detector suite over it, mesh-declared so D9 judges
    coverage even where the jaxpr alone couldn't recover the mesh. Then
    self-test the fire fixtures: each SPMD detector must still PRODUCE
    its warning on a deliberately broken program (unsharded stream /
    gratuitous all-gather / in-program device_put) — a silently-dead
    detector fails the gate like a falsely-firing one."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.distributed import fleet
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    if len(jax.devices()) < 8:
        return [analysis.Finding(
            "spmd-smoke", "error", "spmd/mesh",
            f"the spmd smoke needs >= 8 devices for the tp x dp mesh, got "
            f"{len(jax.devices())} — run through tools/graft_lint.py (it "
            "forces --xla_force_host_platform_device_count=8 before the "
            "backend initializes) or set XLA_FLAGS yourself")]
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().get_mesh()

    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=True, sequence_parallel=True)
    model = LlamaForCausalLM(cfg)
    model = fleet.distributed_model(model)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    paddle.set_flags({"FLAGS_jit_debug_program": True})
    try:
        @paddle.jit.to_static
        def train_step(ids, labels):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rs = np.random.RandomState(1)
        batch, seq = 8, 32
        loss = None
        for _ in range(4):
            ids = paddle.to_tensor(
                rs.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))
            labels = paddle.to_tensor(
                rs.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))
            loss = train_step(ids, labels)
        assert np.isfinite(float(loss)), "spmd train step diverged"

        findings = analysis.audit_compiled(train_step, mesh=mesh,
                                           loc="spmd/train_step")
        vol = analysis.jaxpr_collective_bytes(train_step.program_jaxpr())
        findings.append(analysis.Finding(
            "spmd-smoke", "note", "spmd/train_step",
            f"tp x dp train step compiled on mesh "
            f"{dict(mesh.shape)}; jaxpr-level collective volume "
            f"{vol['total']} B/device over {vol['sites']} site(s) "
            "(GSPMD-inserted collectives live in HLO below the jaxpr)",
            data=vol))
        findings += _audit_partitioner()
    finally:
        paddle.set_flags({"FLAGS_jit_debug_program": False})
    findings += _audit_spmd_fixtures(mesh)
    return findings


def _audit_partitioner() -> list:
    """Round-18 half of the spmd smoke: the DECLARATIVE partitioner
    compiles the UNMODIFIED tiny-LLaMA train step from one
    data+fsdp+tp MeshConfig (no mp_layers wiring), must audit clean
    D1-D11 at default flags with full D9 mesh coverage, and must keep
    its loss on the hand-wired path's trajectory. Then the fire fixture:
    an all-replicated rule table must STILL produce the D9 warning
    through the partitioner path — a silently-dead detector fails the
    gate (the round-15 rule)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.distributed.partitioner import (MeshConfig,
                                                    REPLICATED_RULES,
                                                    partition)
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    def build(mc):
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny_config())
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def train_step(ids, labels):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return model, partition(train_step, mc, model=model)

    mc = MeshConfig(data=2, fsdp=2, tp=2)
    model, step = build(mc)
    rs = np.random.RandomState(1)
    cfg = model.config
    loss = None
    for _ in range(4):
        ids = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (8, 32)).astype("int64"))
        labels = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (8, 32)).astype("int64"))
        loss = step(ids, labels)
    assert np.isfinite(float(loss)), "partitioner train step diverged"

    findings = analysis.audit_compiled(step, loc="spmd/partitioner_step")
    cov = [f for f in findings if f.detector == "spmd-coverage"
           and "coverage ok" in f.message]
    if not cov:
        findings.append(analysis.Finding(
            "spmd-smoke", "error", "spmd/partitioner_step",
            f"the partitioner-driven {mc.describe()} step lost full D9 "
            "mesh-axis stream coverage — the declarative config no "
            "longer shards what it claims"))
    findings += step.plan.to_findings(loc="spmd/partitioner_plan")
    findings.append(analysis.Finding(
        "spmd-smoke", "note", "spmd/partitioner_step",
        f"declarative {mc.describe()} config sharded the unmodified "
        f"tiny-LLaMA train step: {step.plan.summary()}",
        data=step.plan.summary()))

    # fire fixture: REPLICATED_RULES through the same code path must
    # trip the D9 unsharded-stream warning
    paddle.set_flags({"FLAGS_partitioner_heuristics": False})
    try:
        _m, dead = build(MeshConfig(data=2, tp=2, rules=REPLICATED_RULES,
                                    batch_axes=(),
                                    stream_seq_axis="data"))
        for _ in range(4):
            ids = paddle.to_tensor(
                rs.randint(0, cfg.vocab_size, (8, 32)).astype("int64"))
            labels = paddle.to_tensor(
                rs.randint(0, cfg.vocab_size, (8, 32)).astype("int64"))
            dead(ids, labels)
        fired = [f for f in analysis.audit_compiled(
                     dead, loc="spmd/partitioner-fire")
                 if f.detector == "spmd-coverage"
                 and f.severity == "warning"]
    finally:
        paddle.set_flags({"FLAGS_partitioner_heuristics": True})
    if fired:
        findings.append(analysis.Finding(
            "spmd-smoke", "note", "spmd/fire-fixtures",
            "D9 spmd-coverage (all-replicated partitioner rules): fire "
            f"fixture produced {len(fired)} unsuppressed warning(s) — "
            "the detector gates the partitioner path",
            data={"warnings": len(fired)}))
    else:
        findings.append(analysis.Finding(
            "spmd-smoke", "error", "spmd/fire-fixtures",
            "D9 spmd-coverage (all-replicated partitioner rules): the "
            "fire fixture produced NO warning — the detector went "
            "silently dead for partitioner-driven programs"))
    return findings


def _audit_spmd_fixtures(mesh) -> list:
    """Fire-fixture self-test for D9/D10/D11 (see audit_spmd)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import analysis

    # D9: a residual stream explicitly replicated along every mesh axis
    def unsharded(x):
        for _ in range(4):
            x = jax.lax.with_sharding_constraint(
                x + 1.0, NamedSharding(mesh, P(None, None, None)))
        return x

    jx9 = jax.make_jaxpr(unsharded)(jnp.ones((8, 32, 64), jnp.float32))
    d9 = [f for f in analysis.audit_sharding_coverage(jx9, mesh=mesh)
          if f.severity == "warning"]

    # D10: an all_gather whose output only feeds elementwise ops —
    # 128x256 f32 = 131072 B/device, above the default warning floor
    gather_axis = list(mesh.shape)[-1]

    def gratuitous(x):
        g = jax.lax.all_gather(x, gather_axis, axis=0, tiled=True)
        return g * 2.0 + 1.0

    fn = shard_map(gratuitous, mesh=mesh, in_specs=P(gather_axis),
                   out_specs=P(), check_vma=False)
    jx10 = jax.make_jaxpr(fn)(jnp.ones((128, 256), jnp.float32))
    d10 = [f for f in analysis.audit_collectives(jx10)
           if f.severity == "warning"]

    # D11: a device_put inside the program
    def putter(x):
        return jax.device_put(x * 2.0, NamedSharding(mesh, P())) + 1.0

    jx11 = jax.make_jaxpr(putter)(jnp.ones((8, 8), jnp.float32))
    d11 = [f for f in analysis.audit_transfers(jx11)
           if f.severity == "warning"]

    findings = []
    for det, fired in (("D9 spmd-coverage (unsharded stream)", d9),
                       ("D10 spmd-collective (gratuitous all-gather)", d10),
                       ("D11 spmd-transfer (in-program device_put)", d11)):
        if fired:
            findings.append(analysis.Finding(
                "spmd-smoke", "note", "spmd/fire-fixtures",
                f"{det}: fire fixture produced "
                f"{len(fired)} unsuppressed warning(s) — the detector "
                "gates", data={"warnings": len(fired)}))
        else:
            findings.append(analysis.Finding(
                "spmd-smoke", "error", "spmd/fire-fixtures",
                f"{det}: the fire fixture produced NO warning — the "
                "detector went silently dead and sharding regressions "
                "would pass lint"))
    return findings


def audit_conc() -> list:
    """The `conc` smoke (round 17): a genuinely multi-threaded
    serving/ckpt/obs stress with lockdep recording ON — serving ticks on
    the owner thread, a scraper thread hammering the shared /metrics +
    /healthz endpoint, overlapped async checkpoint commits on the saver
    thread, and a comm-watchdog scan loop — then the D14 audit requires
    the recorded lock-ORDER graph to be ACYCLIC with zero
    blocking-under-hot-lock events, and the D15 audit requires zero
    owner-thread contract violations (FLAGS_debug_thread_checks is on
    for the whole stress). Afterwards the fire fixtures self-test every
    detector: a silently-dead detector fails the gate exactly like a
    falsely-firing one (the spmd-smoke rule)."""
    import http.client
    import shutil
    import tempfile
    import threading

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis, ckpt, obs
    from paddle_tpu.core import lockdep
    from paddle_tpu.distributed.comm_watchdog import CommTaskManager
    from paddle_tpu.inference.engine import ServingEngine

    findings = []
    paddle.seed(0)
    model = _tiny_llama()
    lockdep.reset()
    lockdep.enable()
    paddle.set_flags({"FLAGS_debug_thread_checks": True})
    root = tempfile.mkdtemp(prefix="graft_lint_conc_")
    saver = srv = mgr = None
    try:
        eng = ServingEngine(model, max_slots=2)
        srv = obs.shared_server(0)
        srv.register_engine("conc0", eng.registry,
                            ready=lambda: eng.warmed)
        mgr = CommTaskManager(scan_interval=0.01,
                              default_timeout=60.0).start()
        saver = ckpt.AsyncCheckpointer(root)
        stop = threading.Event()
        scrape_errors: list = []
        scrapes = [0]

        def scrape():
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            try:
                while not stop.is_set():
                    for path in ("/metrics", "/healthz"):
                        conn.request("GET", path)
                        conn.getresponse().read()
                        scrapes[0] += 1
            except Exception as e:       # surfaced as a gate error below
                scrape_errors.append(e)
            finally:
                conn.close()

        scraper = threading.Thread(target=scrape, name="conc-scraper",
                                   daemon=True)
        scraper.start()
        rs = np.random.RandomState(0)
        tree = {"w": rs.randn(64).astype("float32")}
        with mgr.watch("conc-smoke"):
            for i, (ln, nt) in enumerate(((3, 2), (6, 4), (4, 3), (5, 2))):
                eng.add_request(rs.randint(0, 128, (ln,)),
                                max_new_tokens=nt)
                while eng.has_work():
                    eng.step()
                saver.save(i + 1, tree)   # overlapped background commit
        saver.wait()
        stop.set()
        scraper.join(timeout=15)
        if scrape_errors:
            findings.append(analysis.Finding(
                "conc-smoke", "error", "conc/stress",
                f"/metrics scraper thread failed mid-stress: "
                f"{scrape_errors[0]!r}"))
        elif scrapes[0] < 2:
            findings.append(analysis.Finding(
                "conc-smoke", "error", "conc/stress",
                "the scraper thread never completed a scrape — the "
                "stress did not actually exercise concurrent reads"))
    finally:
        lockdep.disable()
        paddle.set_flags({"FLAGS_debug_thread_checks": False})
        if saver is not None:
            saver.close()
        if mgr is not None:
            mgr.shutdown()
        if srv is not None:
            srv.close()
        shutil.rmtree(root, ignore_errors=True)

    seen = lockdep.locks_seen()
    if len(seen) < 3:
        findings.append(analysis.Finding(
            "conc-smoke", "error", "conc/stress",
            f"lockdep instrumentation looks dead: only {sorted(seen)} "
            "tracked lock(s) recorded across a serving+scrape+ckpt+"
            "watchdog stress — the wrappers lost their recording hook"))
    else:
        findings.append(analysis.Finding(
            "conc-smoke", "note", "conc/stress",
            f"stress recorded {len(seen)} tracked locks, "
            f"{len(lockdep.lock_graph())} order edge(s), "
            f"{scrapes[0]} concurrent scrapes",
            data={"locks": sorted(seen)}))
    findings += analysis.audit_lock_order(loc="conc/stress")
    findings += analysis.audit_thread_contracts(loc="conc/stress")
    lockdep.reset()
    findings += _audit_conc_fixtures()
    return findings


def _audit_conc_fixtures() -> list:
    """Fire-fixture self-test for D13/D14/D15 (see audit_conc)."""
    import ast as ast_mod
    import threading

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.core import lockdep

    fx = os.path.join(REPO, "tests", "lint_fixtures")

    def _warns(findings):
        return [f for f in findings if f.severity == "warning"]

    p13 = os.path.join(fx, "fx_conc_guarded.py")
    src = open(p13).read()
    d13a = _warns(analysis.lint_guarded_by(
        ast_mod.parse(src), src, "fx_conc_guarded.py"))
    d13b = _warns(analysis.audit_shared_state(
        [os.path.join(fx, "fx_conc_shared.py")], fx))
    d15s = _warns(analysis.audit_contract_callsites(
        [os.path.join(fx, "fx_conc_contract.py")], fx))

    # D14: deterministic two-lock cycle + a blocking call under a hot
    # lock, on scratch lockdep state
    lockdep.reset()
    lockdep.enable()
    la = lockdep.make_lock("fx.A")
    lb = lockdep.make_lock("fx.B", hot=True)
    with la:
        with lb:
            pass
    with lb:
        with la:
            pass
        lockdep.note_blocking("fsync", "fx_conc")
    lockdep.disable()
    d14 = _warns(analysis.audit_lock_order(loc="conc/fire-fixtures"))
    d14_cycle = [f for f in d14 if f.detector == "conc-lock-order"]
    d14_block = [f for f in d14 if f.detector == "conc-blocking-under-lock"]
    lockdep.reset()

    # D15 runtime: a second thread driving a bound contract must BOTH
    # raise ConcurrencyContractError and record an auditable violation
    paddle.set_flags({"FLAGS_debug_thread_checks": True})
    try:
        contract = lockdep.ThreadContract("fx.Engine")
        contract.check("step")              # binds this (owner) thread
        raised: list = []

        def violate():
            try:
                contract.check("step")
            except lockdep.ConcurrencyContractError as e:
                raised.append(e)

        t = threading.Thread(target=violate, name="conc-violator")
        t.start()
        t.join()
        d15r = _warns(analysis.audit_thread_contracts(
            loc="conc/fire-fixtures")) if raised else []
    finally:
        paddle.set_flags({"FLAGS_debug_thread_checks": False})
        lockdep.reset()

    findings = []
    for det, fired in (
            ("D13 conc-guarded-by (unlocked mutations)", d13a),
            ("D13 conc-shared-state (thread-root global)", d13b),
            ("D14 conc-lock-order (two-lock cycle)", d14_cycle),
            ("D14 conc-blocking-under-lock (fsync under hot lock)",
             d14_block),
            ("D15 conc-thread-contract static (root drives engine)",
             d15s),
            ("D15 conc-thread-contract runtime (second thread)", d15r)):
        if fired:
            findings.append(analysis.Finding(
                "conc-smoke", "note", "conc/fire-fixtures",
                f"{det}: fire fixture produced {len(fired)} unsuppressed "
                "warning(s) — the detector gates",
                data={"warnings": len(fired)}))
        else:
            findings.append(analysis.Finding(
                "conc-smoke", "error", "conc/fire-fixtures",
                f"{det}: the fire fixture produced NO warning — the "
                "detector went silently dead and concurrency regressions "
                "would pass lint"))
    return findings


def audit_router() -> list:
    """The `router` smoke (round 20): a REAL 2-replica tiny-LLaMA fleet
    behind the multi-replica Router, with the engines' owner-thread
    contracts enforced (FLAGS_debug_thread_checks on for the whole
    smoke — each replica's driver thread is the only thing allowed to
    drive its engine, and a violation kills the replica, which fails the
    gate below).

    Sequence: both replicas warm through the router's warmup ladder
    (whole-prefill, cache-hit chunk and decode programs at the buckets
    the traffic uses, ending in ``finish_warmup()``) → a shared-prefix
    stream routed by ``prefix_affine`` must concentrate on one replica
    and record fleet prefix-cache hits + ≥1 router affinity hit → a
    drain/handoff ROLLING RESTART mid-stream (replacement admitted only
    after warmup + readiness) with every future completing exactly once
    → gates: ZERO compiles after any replica's warmup barrier (the
    shared AOT executable cache means the replacement warms off r0's
    programs), a clean D17 ``audit_fleet``, every REQUIRED_FLEET_METRICS
    row present in the router's registry, and the affinity-defeat fire
    fixture (a drifting fingerprint on a second fleet) must trip the D17
    warning — a silently-dead detector fails the gate like a falsely
    firing one."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis, obs
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.serving import Router

    findings = []
    paddle.seed(0)
    model = _tiny_llama()
    warm_rs = np.random.RandomState(1)

    def _mk():
        return ServingEngine(model, max_slots=2)

    probe = _mk()
    bs = probe.block_size
    probe.close()
    # warmup prompts share a 2-block prefix of their OWN (distinct from
    # the traffic prefix, so the stream starts cache-cold) but the SAME
    # shapes: request 1 warms the whole-prefill + decode programs,
    # request 2 the cache-hit suffix chunk ladder
    warm_shared = warm_rs.randint(0, 128, (2 * bs + 1,))
    warm_tails = warm_rs.randint(0, 128, (3, 2))

    def _warm(eng):
        # request 1 alone: whole-prefill + single-slot decode buckets
        eng.add_request(np.concatenate([warm_shared, warm_tails[0]]),
                        max_new_tokens=2)
        eng.run()
        # requests 2+3 TOGETHER: the cache-hit suffix chunk ladder and
        # the 2-slot decode bucket the concurrent traffic phase rides
        eng.add_request(np.concatenate([warm_shared, warm_tails[1]]),
                        max_new_tokens=8)
        eng.add_request(np.concatenate([warm_shared, warm_tails[2]]),
                        max_new_tokens=8)
        eng.run()

    paddle.set_flags({"FLAGS_debug_thread_checks": True})
    obs.clear_events()
    router = None
    try:
        router = Router([_mk(), _mk()], policy="prefix_affine",
                        warmup=_warm)
        if not router.wait_ready(300):
            findings.append(analysis.Finding(
                "fleet", "error", "router/fleet-smoke",
                "fleet never became ready: "
                + repr([(n, router.replica(n).state,
                         router.replica(n).error)
                        for n in router.replicas])))
            return findings
        rs = np.random.RandomState(0)
        shared = rs.randint(0, 128, (2 * bs + 1,))
        futs = []
        # phase 1: sequential shared-prefix stream — prefix_affine must
        # concentrate it (deterministic placement, deterministic hits)
        for i in range(6):
            fut = router.submit(
                np.concatenate([shared, rs.randint(0, 128, (2,))]),
                max_new_tokens=2)
            fut.result(120)
            futs.append(fut)
        # phase 2: rolling restart mid-stream — requests in flight on
        # the hot replica finish in place, nothing drops or duplicates
        hot = futs[-1].replica
        for _ in range(4):
            futs.append(router.submit(
                np.concatenate([shared, rs.randint(0, 128, (2,))]),
                max_new_tokens=8))
        new_name = router.drain(hot, replacement=_mk())
        for _ in range(4):
            futs.append(router.submit(
                np.concatenate([shared, rs.randint(0, 128, (2,))]),
                max_new_tokens=2))
        bad = []
        for fut in futs:
            try:
                fut.result(120)
            except Exception as e:      # noqa: BLE001 — gate evidence
                bad.append(repr(e))
            if fut.completions != 1:
                bad.append(f"completions={fut.completions}")
        stats = router.fleet_stats()
        if bad:
            findings.append(analysis.Finding(
                "fleet", "error", "router/fleet-smoke",
                f"rolling restart dropped or duplicated requests: {bad}",
                data={"bad": bad, "stats": stats}))
        else:
            findings.append(analysis.Finding(
                "fleet", "note", "router/fleet-smoke",
                f"14-request shared-prefix stream + drain/handoff of "
                f"{hot} (replacement {new_name}) completed every future "
                "exactly once"))
        if stats["affinity_hits"] < 1 or stats["fleet_prefix_hits"] < 1:
            findings.append(analysis.Finding(
                "fleet", "error", "router/fleet-smoke",
                "prefix_affine routed a shared-prefix stream with "
                f"{stats['affinity_hits']} affinity hit(s) and "
                f"{stats['fleet_prefix_hits']} fleet prefix-cache "
                "hit(s) — affinity placement is not concentrating "
                "shared traffic", data=dict(stats)))
        findings += analysis.audit_fleet(router, loc="router/fleet-smoke")
        snap = router.registry.to_dict()
        missing = [m for m in REQUIRED_FLEET_METRICS if m not in snap]
        if missing:
            findings.append(analysis.Finding(
                "fleet", "error", "router/fleet-smoke",
                f"router registry is missing required fleet metrics: "
                f"{missing}"))
        else:
            findings.append(analysis.Finding(
                "fleet", "note", "router/fleet-smoke",
                f"all {len(REQUIRED_FLEET_METRICS)} required fleet "
                "metrics present"))
        # zero post-warmup compiles per replica: traffic and the
        # replacement's warmup must ride programs the ladder compiled
        evs = [e for e in obs.compile_events()
               if e.site.startswith("serving")]
        findings += obs.audit_recompiles(evs, loc="router/fleet-smoke")
    finally:
        if router is not None:
            router.close()
        paddle.set_flags({"FLAGS_debug_thread_checks": False})

    # ---- D17 affinity-defeat fire fixture: a fleet whose router-side
    # fingerprint DRIFTS (unique hashes for byte-identical prompts —
    # the namespace-mismatch failure mode) must trip the defeat warning
    # through the real counter plumbing. Consumed here as the fixture
    # working; silence is the gate failure.
    fire_router = Router([_mk(), _mk()], policy="prefix_affine",
                         warmup=_warm)
    try:
        if not fire_router.wait_ready(300):
            findings.append(analysis.Finding(
                "fleet", "error", "router/fire-fixture",
                "fire-fixture fleet never became ready"))
            return findings
        drift = iter(range(10 ** 6))
        fire_router._fingerprint = lambda arr: (next(drift),)
        # same shape as the warmup prompts, so the fixture stream rides
        # already-compiled buckets
        prompt = np.random.RandomState(2).randint(
            0, 128, (2 * bs + 3,)).astype(np.int32)
        for _ in range(6):
            fire_router.submit(prompt, max_new_tokens=2).result(120)
        fire = analysis.audit_fleet(fire_router,
                                    loc="router/fire-fixture")
        if any(f.severity == "warning" and "DEFEATED" in f.message
               for f in fire):
            findings.append(analysis.Finding(
                "fleet", "note", "router/fire-fixture",
                "D17 fire fixture verified: a drifting fingerprint "
                "scattered byte-identical prompts and tripped the "
                "affinity-defeat warning"))
        else:
            findings.append(analysis.Finding(
                "fleet", "error", "router/fire-fixture",
                "D17 detector is SILENTLY DEAD: a drifting router "
                "fingerprint scattered repeated prompts with zero "
                "affinity hits and produced no defeat warning",
                data={"findings": [f.to_dict() for f in fire],
                      "stats": fire_router.fleet_stats()}))
    finally:
        fire_router.close()
    return findings


def audit_plan_smoke() -> list:
    """The `plan` smoke (round 21): the static cost model + auto-plan
    search gated end-to-end on the 8-device virtual mesh.

    Sequence: `autoplan.search` enumerates + ranks every valid
    MeshConfig for a tiny-LLaMA train step from ONE abstract lowering
    (nothing executes) — fewer than 6 valid candidates is a gate error
    → D18 ``audit_plan`` must be clean on the report's own top-1 →
    the three partitioner_scaling configs (data8 / data4×tp2 /
    data2×sep4) are ACTUALLY measured (3 warmup + 2 timed steps each)
    and D19 ``audit_cost_model_calibration`` gates the predicted
    ordering against measured tok/s at default tolerance → fire
    fixtures: D18 must warn when the WORST candidate is deployed and
    error on a rigged HBM budget, and D19 must fire on a rigged-fabric
    search (tp/sep on a free DCN, ICI throttled to nothing) that flips
    the predicted ranking against the same measurements — a silently
    dead detector fails the gate like a falsely firing one."""
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.distributed.partitioner import (MeshConfig, autoplan,
                                                    partition)
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    findings = []
    batch, seq = 8, 64
    paddle.seed(0)
    cfg = llama_tiny_config(max_position_embeddings=128)
    report = autoplan.search(LlamaForCausalLM(cfg), 8, batch=batch,
                             seq=seq)
    findings += report.findings
    if len(report.candidates) < 6:
        findings.append(analysis.Finding(
            "plan", "error", "plan/search",
            f"auto-plan search found only {len(report.candidates)} valid "
            "candidate(s) on the 8-device virtual mesh (>= 6 expected "
            "for tiny-LLaMA) — the enumerator or the rule-table guards "
            "regressed",
            data={"rejected": report.rejected}))
        return findings
    findings.append(analysis.Finding(
        "plan", "note", "plan/search",
        f"ranked {len(report.candidates)} valid candidate(s) "
        f"({len(report.rejected)} rejected) from one abstract lowering; "
        f"top-1 {report.chosen}"))
    findings += analysis.audit_plan(report, loc="plan/search")

    # ---- measure the three partitioner_scaling configs (the bench
    # rung's well-separated trio) so D19 compares prediction against
    # REAL steps, not against another model
    measured = {}
    for mc in (MeshConfig(data=8), MeshConfig(data=4, tp=2),
               MeshConfig(data=2, sep=4)):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def step(ids, labels, model=model, opt=opt):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        pstep = partition(step, mc, model=model)
        rs = np.random.RandomState(0)

        def batch_pair():
            return (paddle.to_tensor(rs.randint(
                        0, cfg.vocab_size,
                        (batch, seq)).astype("int64")),
                    paddle.to_tensor(rs.randint(
                        0, cfg.vocab_size,
                        (batch, seq)).astype("int64")))

        for _ in range(3):                 # eager/discovery/compile
            float(pstep(*batch_pair()))
        t0 = time.perf_counter()
        for _ in range(2):
            float(pstep(*batch_pair()))
        wall = time.perf_counter() - t0
        measured[mc.describe()] = 2 * batch * seq / wall
    findings += analysis.audit_cost_model_calibration(
        report, measured, loc="plan/calibration")

    # ---- D18 fire fixtures through the REAL report: deploying the
    # worst-ranked candidate must warn, a rigged HBM budget must error
    worst = report.candidates[-1].config
    fire = analysis.audit_plan(report, chosen=worst, regress_pct=5.0,
                               loc="plan/fire-d18")
    if any(f.severity == "warning" for f in fire):
        findings.append(analysis.Finding(
            "plan", "note", "plan/fire-d18",
            f"D18 fire fixture verified: deploying the worst candidate "
            f"({worst.describe()}) tripped the plan-regression warning"))
    else:
        findings.append(analysis.Finding(
            "plan", "error", "plan/fire-d18",
            "D18 detector is SILENTLY DEAD: the worst-ranked candidate "
            "deployed against a 5% regression budget produced no "
            "warning",
            data={"findings": [f.to_dict() for f in fire]}))
    fire = analysis.audit_plan(report, hbm_limit_mb=0.001,
                               loc="plan/fire-d18")
    if not any(f.severity == "error" for f in fire):
        findings.append(analysis.Finding(
            "plan", "error", "plan/fire-d18",
            "D18 detector is SILENTLY DEAD: a 0.001 MiB HBM budget "
            "produced no over-budget error",
            data={"findings": [f.to_dict() for f in fire]}))

    # ---- D19 fire fixture: rig the fabrics (tp/sep collectives on a
    # free DCN, ICI throttled to nothing) so the grad psum dominates
    # and the predicted ranking FLIPS among the measured trio — the
    # calibration detector must catch the misordering
    rig = {"FLAGS_analysis_ici_gbps": 1e-4,
           "FLAGS_analysis_dcn_gbps": 1e6,
           "FLAGS_analysis_dcn_alpha_us": 0.0}
    saved = paddle.get_flags(list(rig))
    paddle.set_flags(rig)
    try:
        paddle.seed(0)
        rigged = autoplan.search(
            LlamaForCausalLM(cfg), 8, batch=batch, seq=seq,
            candidates=[MeshConfig(data=8, dcn_axes=("tp", "sep")),
                        MeshConfig(data=4, tp=2, dcn_axes=("tp", "sep")),
                        MeshConfig(data=2, sep=4,
                                   dcn_axes=("tp", "sep"))])
    finally:
        paddle.set_flags(saved)
    fire = analysis.audit_cost_model_calibration(
        rigged, measured, tol_pct=0.0, loc="plan/fire-d19")
    if rigged.chosen == report.chosen:
        findings.append(analysis.Finding(
            "plan", "error", "plan/fire-d19",
            f"rigged fabrics did not flip the predicted ranking (top-1 "
            f"still {rigged.chosen}) — the alpha-beta model is not "
            "reading the axis->fabric mapping",
            data={"rigged": [c.describe for c in rigged.candidates]}))
    elif any(f.severity == "error" for f in fire):
        findings.append(analysis.Finding(
            "plan", "note", "plan/fire-d19",
            f"D19 fire fixture verified: rigged fabrics flipped the "
            f"predicted top-1 to {rigged.chosen} and the calibration "
            "detector caught the misordering against measured tok/s"))
    else:
        findings.append(analysis.Finding(
            "plan", "error", "plan/fire-d19",
            "D19 detector is SILENTLY DEAD: a rigged-fabric search "
            "misordered the measured configs and the calibration audit "
            "stayed clean",
            data={"rigged_top1": rigged.chosen,
                  "findings": [f.to_dict() for f in fire]}))
    return findings


def audit_quant() -> list:
    """The `quant` smoke (round 20): drive int8 and int4 weight-only
    paged engines plus an int4-KV engine against a full-precision twin
    ON THE SAME STREAM, then gate the quantization claims:

    - D20 audit_quantized_bytes over the REAL decode-program ledger
      rows: the int8 engine's measured weight traffic must shrink
      >= 1.8x, the int4 engine's >= 3.4x, vs the twin (weight bytes
      from engine.param_bytes — the packed stack, scales included);
    - D20b audit_silent_dequant + D1/D4 on the quantized decode
      program's jaxpr;
    - zero compiles after the warmup barrier on every quantized engine
      (a per-mode cache-key miss recompiling mid-serve is D6);
    - fire fixtures for both detectors — a rigged non-shrinking ledger
      pair and a weight-sized int8->f32 convert must each trip an
      error; silence is the gate failure."""
    import types

    import numpy as np

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import analysis, obs
    from paddle_tpu.core.flags import flag
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.obs import costs as _costs

    paddle.seed(0)
    model = _tiny_llama()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (ln,)) for ln in (5, 9)]
    findings = []

    obs.clear_events()

    def drive(wq, kv):
        eng = ServingEngine(model, max_slots=2, weight_quant=wq,
                            kv_cache_dtype=kv)
        for p in prompts:
            eng.add_request(p, max_new_tokens=4)
        eng.run()                        # warm this mode's programs
        eng = ServingEngine(model, max_slots=2, weight_quant=wq,
                            kv_cache_dtype=kv)
        eng.finish_warmup()
        for p in prompts:
            eng.add_request(p, max_new_tokens=4)
        out = eng.run()
        assert len(out) == len(prompts) and all(
            len(v) for v in out.values()), \
            f"quant smoke engine (w={wq}, kv={kv}) failed to drain"
        return eng

    eng_full = drive("none", "model")
    eng_i8 = drive("int8", "model")
    eng_i4 = drive("int4", "model")
    eng_kv = drive("int4", "int4")

    # ---- D20: the ledger arithmetic on the real decode programs. The
    # twin pair shares bucket + sampling + KV mode, so every non-weight
    # byte cancels and the difference isolates the weight stream. The
    # audit runs on PROGRAM-BOUNDARY bytes (args + outputs): that is the
    # HBM traffic a bandwidth-bound decode step must move, and it is
    # platform-stable — this smoke runs on the CPU XLA fallback, whose
    # per-instruction bytes_accessed re-buys the materialized dequant
    # intermediate the fused TPU kernel keeps in VMEM. The failure modes
    # D20 exists for (a cache keyed without the quant mode serving the
    # bf16 program; a packed tensor shipped next to its dequantized
    # copy) all land in the boundary bytes.
    def decode_row(wq, kv):
        rows = [e for e in _costs.ledger("serving.decode")
                if f"/kv{kv}/w{wq}" in e.program and e.analyzed]
        return max(rows, key=lambda e: e.bytes_accessed, default=None)

    full_row = decode_row("none", "model")
    decls, boundary = [], []
    for mode, eng in (("int8", eng_i8), ("int4", eng_i4)):
        row = decode_row(mode, "model")
        if row is None or full_row is None:
            findings.append(analysis.Finding(
                "quant-bytes", "error", "quant/ledger",
                f"decode program rows missing from the cost ledger "
                f"(mode {mode}: {row is not None}, twin: "
                f"{full_row is not None}) — the engines never recorded "
                "analyzed programs", data={"mode": mode}))
            continue
        decls.append({"program": row.program, "twin": full_row.program,
                      "mode": mode,
                      "weight_bytes_full": eng_full.param_bytes})
        boundary.append(row)
    if decls:
        boundary.append(full_row)
        entries = [types.SimpleNamespace(
            program=e.program, analyzed=e.analyzed,
            bytes_accessed=e.arg_bytes + e.out_bytes) for e in boundary]
        d20 = analysis.audit_quantized_bytes(decls, entries=entries,
                                             loc="quant/ledger")
    else:
        d20 = []
    findings += d20
    if decls and not d20:
        findings.append(analysis.Finding(
            "quant-bytes", "note", "quant/ledger",
            f"D20 verified on {len(decls)} live decode-program pair(s): "
            "int8/int4 weight traffic within budget vs the "
            "full-precision twin",
            data={"declarations": [d["program"] for d in decls]}))

    # ---- jaxpr-side audits on the quantized decode program: silent
    # f32 dequant, fusion misses, host callbacks, stream dtype
    for tag, eng in (("int4w", eng_i4), ("int4kv", eng_kv)):
        jx = eng.decode_program_jaxpr()
        findings += analysis.audit_silent_dequant(
            jx, loc=f"quant/decode_step[{tag}]")
        findings += analysis.audit_fusion_misses(
            jx, loc=f"quant/decode_step[{tag}]")
        findings += analysis.audit_callbacks(
            jx, loc=f"quant/decode_step[{tag}]")
        findings += analysis.audit_dtype_stream(
            jx, policy=str(flag("FLAGS_residual_dtype")),
            loc=f"quant/decode_step[{tag}]")

    # ---- D6: the measured drives above ran after finish_warmup() on
    # engines whose programs the warm drives compiled — any serving
    # compile after a warmup barrier is a per-mode cache-key bug
    evs = [e for e in obs.compile_events() if e.site.startswith("serving")]
    findings += obs.audit_recompiles(evs, loc="quant/post-warmup")

    # ---- D20 fire fixture: a declared-int4 program whose ledger bytes
    # never shrank must trip the budget error (and a declaration over a
    # ledger that never analyzed the program must also fail)
    wfull = 100e6
    rig = [types.SimpleNamespace(program="fix|decode/q", analyzed=True,
                                 bytes_accessed=120e6),
           types.SimpleNamespace(program="fix|decode/full", analyzed=True,
                                 bytes_accessed=121e6)]
    fire = analysis.audit_quantized_bytes(
        [{"program": "fix|decode/q", "twin": "fix|decode/full",
          "mode": "int4", "weight_bytes_full": wfull}],
        entries=rig, loc="quant/fire-d20")
    missing = analysis.audit_quantized_bytes(
        [{"program": "fix|nowhere", "twin": "fix|decode/full",
          "mode": "int8", "weight_bytes_full": wfull}],
        entries=rig, loc="quant/fire-d20")
    if any(f.severity == "error" for f in fire) and \
            any(f.severity == "error" for f in missing):
        findings.append(analysis.Finding(
            "quant-bytes", "note", "quant/fire-d20",
            "D20 fire fixtures verified: the non-shrinking ledger pair "
            "tripped the byte-budget error and the never-analyzed "
            "declaration tripped the dead-audit error"))
    else:
        findings.append(analysis.Finding(
            "quant-bytes", "error", "quant/fire-d20",
            "D20 detector is SILENTLY DEAD: a declared-int4 program "
            "moving full-width bytes (or a declaration over a ledger "
            "that never saw it) produced no error",
            data={"fire": [f.to_dict() for f in fire],
                  "missing": [f.to_dict() for f in missing]}))

    # ---- D20b fire fixture: a weight-sized int8 -> f32 convert inside
    # a program must trip the silent-dequant error
    def dequant_to_f32(q, s):
        return q.astype(jnp.float32) * s

    jx_fire = jax.make_jaxpr(dequant_to_f32)(
        jnp.zeros((1024, 1024), jnp.int8), jnp.float32(0.1))
    fire = analysis.audit_silent_dequant(jx_fire, loc="quant/fire-d20b")
    if any(f.severity == "error" for f in fire):
        findings.append(analysis.Finding(
            "quant-bytes", "note", "quant/fire-d20b",
            "D20b fire fixture verified: a 1M-element int8->f32 "
            "convert_element_type tripped the silent-dequant error"))
    else:
        findings.append(analysis.Finding(
            "quant-bytes", "error", "quant/fire-d20b",
            "D20b detector is SILENTLY DEAD: a weight-sized int8->f32 "
            "convert produced no silent-dequant error",
            data={"findings": [f.to_dict() for f in fire]}))
    return findings


#: the baseline entries (with their `_matched` counts) of the most
#: recent run() — the --json payload exposes them so a PARALLEL gate
#: (check_scoreboard.lint_gate round 17: one subprocess per smoke group)
#: can aggregate staleness across partial runs instead of losing it
LAST_BASELINE: list = []


def run(models=(), ast=True, baseline_path=DEFAULT_BASELINE,
        prune_baseline=False, defer_stale=False):
    global LAST_BASELINE

    from paddle_tpu import analysis

    findings = []
    if ast:
        # the static (no-trace) audits ride the AST pass: in the
        # parallel CI gate exactly ONE group runs them, so a tune-cache
        # warning is reported once, not once per worker
        findings += analysis.lint_tree(REPO)
        findings += analysis.audit_tune_cache()
    smokes = {"paged": audit_serving, "obs": audit_obs,
              "ckpt": audit_ckpt, "spmd": audit_spmd, "conc": audit_conc,
              "router": audit_router, "plan": audit_plan_smoke,
              "quant": audit_quant}
    for name in models:
        findings += smokes.get(name, lambda n=name: audit_model(n))()
    baseline = analysis.load_baseline(baseline_path)
    analysis.apply_baseline(findings, baseline)
    LAST_BASELINE = baseline
    if defer_stale:
        # the caller (the parallel CI gate) aggregates staleness over
        # the union of its partial runs via the --json baseline counts
        return findings

    # stale-suppression detection: an entry that suppressed nothing can
    # only mask a future real finding. On a FULL-coverage run (AST lint +
    # every CI smoke) that is a gate failure; on a partial run it is
    # informational (model-specific entries legitimately go unmatched).
    stale = analysis.stale_suppressions(baseline)
    full = ast and set(CI_MODELS) <= set(models)
    if stale and prune_baseline:
        if not full:
            findings.append(analysis.Finding(
                "stale-suppression", "error", baseline_path,
                "--prune-baseline requires a full-coverage run (--models "
                f"{','.join(CI_MODELS)} with the AST lint on): a partial "
                "run cannot tell a dead suppression from one whose smoke "
                "did not compile"))
        else:
            kept = [{k: v for k, v in e.items() if not k.startswith("_")}
                    for e in baseline if e.get("_matched")]
            with open(baseline_path, "w") as fh:
                json.dump({"suppressions": kept}, fh, indent=2)
                fh.write("\n")
            for e in stale:
                findings.append(analysis.Finding(
                    "stale-suppression", "note", baseline_path,
                    f"pruned stale suppression (matched zero findings): "
                    f"detector={e['detector']!r} match={e['match']!r}",
                    data={k: v for k, v in e.items()
                          if not k.startswith("_")}))
            stale = []
    for e in stale:
        findings.append(analysis.Finding(
            "stale-suppression", "warning" if full else "note",
            baseline_path,
            f"suppression matched zero findings this run: "
            f"detector={e['detector']!r} match={e['match']!r}"
            + (f" (reason: {e['reason']})" if e.get("reason") else "")
            + (" — remove it or rerun with --prune-baseline" if full else
               " — partial run; rerun with the full CI model set to "
               "confirm staleness"),
            data={k: v for k, v in e.items() if not k.startswith("_")}))
    return findings


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--models", default="",
                    help="comma-separated smoke configs to audit "
                         f"({','.join(CI_MODELS)})")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"suppression file (default {DEFAULT_BASELINE})")
    ap.add_argument("--no-ast", action="store_true",
                    help="skip the static audits (AST lint + tune-cache "
                         "scan) — model/jaxpr audits only")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="rewrite the baseline without entries that "
                         "matched zero findings (full-coverage runs only)")
    ap.add_argument("--defer-stale", action="store_true",
                    help="emit no stale-suppression findings; the --json "
                         "payload carries per-entry match counts so a "
                         "parallel caller can aggregate staleness over "
                         "the union of partial runs")
    args = ap.parse_args(argv)

    # every smoke runs on the same virtual 8-device CPU platform the test
    # suite uses (tests/conftest.py): the spmd smoke needs the mesh, the
    # others behave identically — must happen before the backend
    # initializes, i.e. before paddle_tpu imports
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8").strip()
    if os.environ["JAX_PLATFORMS"] == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")

    models = [m for m in args.models.split(",") if m]
    from paddle_tpu import analysis

    if os.environ["JAX_PLATFORMS"] == "cpu":
        from paddle_tpu.obs.peaks import set_off_chip_peaks

        set_off_chip_peaks()    # the CPU is in no peaks table

    findings = run(models=models, ast=not args.no_ast,
                   baseline_path=args.baseline,
                   prune_baseline=args.prune_baseline,
                   defer_stale=args.defer_stale)
    if args.as_json:
        payload = analysis.to_json(findings)
        payload["baseline"] = [
            {"detector": e.get("detector"), "match": e.get("match"),
             "matched": e.get("_matched", 0)} for e in LAST_BASELINE]
        payload["models"] = models
        payload["ast"] = not args.no_ast
        print(json.dumps(payload, indent=2))
    else:
        print(analysis.format_text(findings))
    return 1 if analysis.gate_failures(findings) else 0


if __name__ == "__main__":
    raise SystemExit(main())
