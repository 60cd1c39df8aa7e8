"""paddle.inference — deployment predictor (L13).

Reference parity: AnalysisPredictor / AnalysisConfig / create_predictor
(/root/reference/paddle/fluid/inference/api/analysis_predictor.h:101,
paddle_inference_api.h): load a saved program + params, run the analysis
pass pipeline, serve zero-copy Run() calls.

TPU-native design (SURVEY §7 "AOT-compiled StableHLO serving"): the saved
artifact is paddle.jit.save's serialized StableHLO (+ pickled state_dict);
"analysis passes" ARE XLA's AOT pipeline — deserialization hands back a
compiled executable, so Predictor.run is one XLA invocation with no Python
op dispatch. Where only the state_dict exists, the predictor falls back to
re-jitting the registered network class once (first call compiles).
"""
from __future__ import annotations

import enum
import os

import numpy as np


class PrecisionType(enum.Enum):
    Float32 = 0
    Half = 1      # maps to bfloat16 on TPU
    Bfloat16 = 2
    Int8 = 3


class Config:
    """≙ AnalysisConfig: model paths + device + precision switches."""

    def __init__(self, prog_file: str | None = None, params_file: str | None = None):
        # paddle passes either (model_dir) or (prog, params); we accept the
        # jit.save prefix in either slot
        self._prefix = None
        if prog_file is not None:
            self._prefix = prog_file[:-len(".stablehlo")] \
                if prog_file.endswith(".stablehlo") else prog_file
        self._check_params_file(params_file)
        self._device = "tpu"
        self._device_id = 0
        self._precision = PrecisionType.Float32
        self._enable_memory_optim = True
        self._network_factory = None
        self._ir_optim = True
        self._profile = False
        self._cpu_threads = 1

    # -- device selection (parity names)
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._device, self._device_id = "tpu", device_id  # tpu-native alias
        self._precision = precision

    def enable_xpu(self, *a, **k):
        self._device = "tpu"

    def disable_gpu(self):
        self._device = "cpu"

    def set_model(self, prog_file, params_file=None):
        self._prefix = prog_file[:-len(".stablehlo")] \
            if prog_file.endswith(".stablehlo") else prog_file
        self._check_params_file(params_file)

    def _check_params_file(self, params_file):
        """jit.save bundles weights with the StableHLO artifact at the same
        prefix; a separate params_file is accepted for reference-API parity
        but must agree with the program prefix."""
        import os

        if params_file is None or self._prefix is None:
            return
        base = os.path.splitext(params_file)[0]
        if base != self._prefix:
            raise ValueError(
                f"params_file {params_file!r} does not match the program "
                f"prefix {self._prefix!r}; this build loads weights from "
                "the jit.save artifact at the program prefix")

    def set_network_factory(self, factory):
        """TPU extension: zero-arg callable rebuilding the network — the
        fallback when no serialized StableHLO exists for this artifact."""
        self._network_factory = factory

    def enable_paged_serving(self, slots=None, kv_block_size=None,
                             kv_cache_dtype=None, num_kv_blocks=None,
                             max_model_len=None):
        """Serve generation through the continuous-batching paged-KV
        engine (inference/engine.py) instead of one-shot Run() calls —
        consumed by create_serving_predictor. None keeps each knob at its
        FLAGS_* default (FLAGS_serving_slots, FLAGS_kv_block_size,
        FLAGS_kv_cache_dtype)."""
        self._serving = {"max_slots": slots, "kv_block_size": kv_block_size,
                         "kv_cache_dtype": kv_cache_dtype,
                         "num_kv_blocks": num_kv_blocks,
                         "max_model_len": max_model_len}

    def enable_memory_optim(self, flag=True):
        """REAL effect on the network-factory path: predictor inputs are
        donated to the compiled program (the XLA analog of the reference's
        memory-reuse pass)."""
        self._enable_memory_optim = flag

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag  # XLA always optimizes; stored for summary

    def enable_profile(self):
        self._profile = True

    def disable_glog_info(self):
        return None

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_threads = int(n)

    def model_dir(self):
        return self._prefix

    def summary(self) -> str:
        return (f"Config(prefix={self._prefix}, device={self._device}, "
                f"precision={self._precision.name}, "
                f"memory_optim={self._enable_memory_optim})")


class Tensor:
    """≙ paddle_infer::Tensor — named zero-copy handle."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def copy_from_cpu(self, arr: np.ndarray):
        self._value = np.asarray(arr)

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    def copy_to_cpu(self) -> np.ndarray:
        import jax

        return np.asarray(jax.device_get(self._value))


def _network_from_factory(config: Config):
    """Shared Predictor/ServingPredictor load path: rebuild the network
    from the factory, load weights from the artifact prefix (loud
    FileNotFoundError on a wrong path — never silently serve random
    init), apply the precision switch."""
    from ..framework_io import load as _load_obj

    if config.model_dir() is None:
        raise ValueError("Config has no model path")
    payload = _load_obj(config.model_dir() + ".pdparams")
    net = config._network_factory()
    net.set_state_dict(payload.get("state_dict", payload))
    net.eval()
    if config._precision in (PrecisionType.Half, PrecisionType.Bfloat16):
        # REAL precision switch: serve in bf16 (params cast once at
        # load — the analog of the reference's fp16 analysis pass)
        from .. import amp

        net = amp.decorate(net, None, level="O2", dtype="bfloat16")
    elif config._precision == PrecisionType.Int8:
        raise NotImplementedError(
            "Int8 serving needs a quantized export "
            "(paddle.quantization PTQ) — not an inference-time "
            "switch on TPU")
    return net


class Predictor:
    def __init__(self, config: Config):
        self.config = config
        prefix = config.model_dir()
        if prefix is None:
            raise ValueError("Config has no model path")
        self._exported = None
        self._layer = None
        hlo = prefix + ".stablehlo"
        if os.path.exists(hlo):
            import jax.export as jexport

            with open(hlo, "rb") as f:
                self._exported = jexport.deserialize(f.read())
            self._n_inputs = len(self._exported.in_avals)
        elif config._network_factory is not None:
            self._layer = _network_from_factory(config)
            self._n_inputs = None
        else:
            raise FileNotFoundError(
                f"no serialized program at {hlo}; pass "
                "Config.set_network_factory to serve from the state_dict")
        self._inputs: dict[str, Tensor] = {}
        self._outputs: list[np.ndarray] = []
        self._compiled: dict = {}    # input signature -> (jitted, params)
        self._run_times: list[float] = []

    # -- paddle_infer API
    def get_input_names(self):
        n = self._n_inputs if self._n_inputs is not None else 1
        return [f"input_{i}" for i in range(n)]

    def get_input_handle(self, name) -> Tensor:
        return self._inputs.setdefault(name, Tensor(name))

    def get_output_names(self):
        return [f"output_{i}" for i in range(max(len(self._outputs), 1))]

    def get_output_handle(self, name) -> Tensor:
        idx = int(name.rsplit("_", 1)[1])
        t = Tensor(name)
        t._value = self._outputs[idx]
        return t

    def _compiled_layer_call(self, inputs):
        """Network-factory path: ONE jitted XLA program per input signature
        (the AOT 'analysis' product), inputs donated when
        enable_memory_optim — this is where the Config switches become real
        behavior instead of stored fields."""
        import jax

        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor as PTensor

        inputs = [np.asarray(a) for a in inputs]
        key = tuple((tuple(a.shape), str(a.dtype)) for a in inputs)
        exe = self._compiled.get(key)
        if exe is None:
            params = [p for p in self._layer.parameters()]

            def pure(param_datas, arg_datas):
                saved = [p._data for p in params]
                for p, d in zip(params, param_datas):
                    p._data = d
                try:
                    with no_grad():
                        res = self._layer(*[
                            PTensor(d, _internal=True, stop_gradient=True)
                            for d in arg_datas])
                    if isinstance(res, (list, tuple)):
                        return [r._data for r in res]
                    return [res._data]
                finally:
                    for p, d in zip(params, saved):
                        p._data = d

            donate = (1,) if self.config._enable_memory_optim else ()
            exe = (jax.jit(pure, donate_argnums=donate), params)
            self._compiled[key] = exe
        jitted, params = exe
        return jitted([p._data for p in params], inputs)

    def run(self, inputs: list[np.ndarray] | None = None):
        """Execute the compiled program. With `inputs` given, returns the
        outputs directly (paddle_infer also supports the handle API)."""
        import time

        t0 = time.perf_counter() if self.config._profile else None
        if inputs is None:
            names = self.get_input_names()
            inputs = [self._inputs[n]._value for n in names]
        if self._exported is not None:
            out = self._exported.call(*[np.asarray(a) for a in inputs])
            outs = list(out) if isinstance(out, (list, tuple)) else [out]
        else:
            outs = self._compiled_layer_call(inputs)
        self._outputs = outs
        if t0 is not None:
            # profile timings must include device completion
            import jax

            jax.block_until_ready(outs)
            self._run_times.append(time.perf_counter() - t0)
        return outs

    def get_profile_summary(self) -> dict:
        ts = self._run_times
        if not ts:
            return {"runs": 0}
        return {"runs": len(ts), "avg_ms": 1e3 * sum(ts) / len(ts),
                "min_ms": 1e3 * min(ts), "max_ms": 1e3 * max(ts)}

    def try_shrink_memory(self):
        import gc

        self._compiled.clear()
        gc.collect()
        return None

    def clear_intermediate_tensor(self):
        return None


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


class ServingPredictor:
    """paddle_infer-style deployment wrapper over the continuous-batching
    engine: load the model the same way Predictor's network-factory path
    does (state_dict at the artifact prefix), then serve generation
    requests through a shared ServingEngine — the deployment surface of
    the paged decode stack (engine API itself: inference/engine.py)."""

    def __init__(self, config: Config, model=None):
        from .engine import ServingEngine

        self.config = config
        if model is None:
            if config._network_factory is None:
                raise ValueError(
                    "ServingPredictor needs Config.set_network_factory "
                    "(or an explicit model) to build the network")
            model = _network_from_factory(config)
        kw = {k: v for k, v in getattr(config, "_serving", {}).items()
              if v is not None}
        self.engine = ServingEngine(model, **kw)

    def add_request(self, prompt, **sampling) -> int:
        return self.engine.add_request(prompt, **sampling)

    def step(self):
        return self.engine.step()

    def generate(self, prompts, **sampling):
        """Batch convenience: queue every prompt, drain the engine, and
        return a list of generated-token arrays in prompt order."""
        rids = [self.add_request(p, **sampling) for p in prompts]
        done = self.engine.run()
        return [done[r] for r in rids]

    def get_stats(self) -> dict:
        return self.engine.stats()

    def metrics(self) -> dict:
        """The engine's obs registry snapshot (counters/gauges + TTFT /
        queue-wait / TPOT histogram quantiles) — the machine-readable
        twin of get_stats(); same numbers the /metrics endpoint
        (FLAGS_obs_http_port) exposes in Prometheus text form."""
        return self.engine.metrics()

    def render_prometheus(self) -> str:
        return self.engine.render_prometheus()


def create_serving_predictor(config: Config, model=None) -> ServingPredictor:
    return ServingPredictor(config, model)


class PredictorPool:
    """≙ paddle_infer::services::PredictorPool — N predictors over one
    loaded artifact (thread-per-request serving)."""

    def __init__(self, config: Config, size: int = 1):
        self._preds = [Predictor(config) for _ in range(max(1, int(size)))]

    def retrieve(self, idx: int) -> Predictor:
        return self._preds[idx % len(self._preds)]
