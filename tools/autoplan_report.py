#!/usr/bin/env python
"""autoplan_report — rank MeshConfigs for a model BEFORE anything runs.

Drives `paddle_tpu.distributed.partitioner.autoplan.search`: one
abstract lowering of the model's train step (jax.make_jaxpr — nothing
executes, no devices are touched), then every MeshConfig that survives
the rule-table guards is scored by the static cost model
(paddle_tpu/analysis/costmodel.py): roofline compute/HBM at
FLAGS_obs_peak_tflops / FLAGS_obs_peak_gbps, an alpha-beta ICI/DCN
collective bill (FLAGS_analysis_ici_gbps / FLAGS_analysis_dcn_gbps and
their alpha flags; axis→fabric per MeshConfig.dcn_axes), and a
liveness peak-HBM pass honoring donation and per-device shard sizes.
Candidates over FLAGS_analysis_hbm_limit_mb are rejected statically
with a named `plan-hbm` Finding — an OOM caught here, not on the pod.

The table is the same PlanReport the graft_lint `plan` smoke and the
bench `autoplan` rung gate with D18 (audit_plan) / D19
(audit_cost_model_calibration).

Usage:
    python tools/autoplan_report.py                    # tiny-LLaMA, 8 dev
    python tools/autoplan_report.py --devices 16 --batch 16 --seq 256
    python tools/autoplan_report.py --hidden 2048 --layers 22 --heads 16
    python tools/autoplan_report.py --hbm-limit-mb 96 --json
    python tools/autoplan_report.py --dcn-axes data     # data axis on DCN
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="pod size to plan for (default 8 — matches the "
                         "virtual CPU mesh this tool forces off-chip)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=128,
                    help="model width (tiny-LLaMA geometry flags — the "
                         "plan is a function of shapes only)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--intermediate", type=int, default=0,
                    help="MLP width (default 2*hidden)")
    ap.add_argument("--no-sep", action="store_true",
                    help="skip sep (context-parallel) candidates")
    ap.add_argument("--dcn-axes", default="",
                    help="comma-separated mesh axes that cross the DCN "
                         "(slow fabric) instead of ICI")
    ap.add_argument("--hbm-limit-mb", type=float, default=None,
                    help="reject candidates whose predicted peak HBM "
                         "exceeds this (default "
                         "FLAGS_analysis_hbm_limit_mb; 0 = off)")
    ap.add_argument("--top", type=int, default=0,
                    help="print only the best N candidates (0 = all)")
    ap.add_argument("--json", dest="as_json", action="store_true")
    args = ap.parse_args(argv)

    # planning is abstract, but building the model needs a backend —
    # force the same virtual CPU platform the test suite / lint smokes
    # use so this tool runs identically on a dev box and on the pod host
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8").strip()
    if os.environ["JAX_PLATFORMS"] == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.distributed.partitioner import autoplan

    if os.environ["JAX_PLATFORMS"] == "cpu":
        # the plan is FOR the chip: price it at the v5e row of the peaks
        # table, which this host's CPU is not in — unless flags say else
        row = obs.device_peaks("TPU v5 lite")
        for name, val in (("FLAGS_obs_peak_tflops", row["bf16_tflops"]),
                          ("FLAGS_obs_peak_gbps", row["hbm_gbps"])):
            if not paddle.get_flags(name)[name]:
                paddle.set_flags({name: val})
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    paddle.seed(0)
    cfg = llama_tiny_config(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=args.intermediate or 2 * args.hidden,
        num_hidden_layers=args.layers, num_attention_heads=args.heads,
        max_position_embeddings=max(args.seq, 128))
    model = LlamaForCausalLM(cfg)
    dcn = tuple(a for a in args.dcn_axes.split(",") if a)
    report = autoplan.search(model, args.devices, batch=args.batch,
                             seq=args.seq, include_sep=not args.no_sep,
                             hbm_limit_mb=args.hbm_limit_mb,
                             dcn_axes=dcn)
    if args.top > 0:
        report.candidates = report.top(args.top)
    if args.as_json:
        print(json.dumps(report.to_dict(), indent=2))
    else:
        print(report.format_text())
        for f in report.findings:
            print(f"[{f.severity}/{f.detector}] {f.loc}: {f.message}")
    return 0 if report.candidates else 1


if __name__ == "__main__":
    raise SystemExit(main())
