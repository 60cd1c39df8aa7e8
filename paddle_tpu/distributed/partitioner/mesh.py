"""MeshConfig — the ONE declarative object that sizes a pod run.

Reference parity: the t5x/GSPMD partitioning layer (SNIPPETS.md [1]-[3]:
`MeshConfig` + logical-axis rules + `pjit_with_cpu_fallback`). The
reference ecosystem sizes hybrid parallelism through fleet
`hybrid_configs` dicts wired per model (`dp_degree`/`mp_degree` +
per-model mp_layers); here one frozen dataclass names the mesh axes

    data  — batch sharding (pure data parallel)
    fsdp  — ZeRO-3 axis: parameters are stored sharded along it and the
            batch is split over it too; GSPMD inserts the per-use
            all-gather of params and the reduce-scatter of grads
    tp    — tensor axis: vocab/heads/mlp weight dims + the
            sequence-parallel stream placement between blocks
    sep   — context-parallel axis: activations sequence-sharded, the
            attention-time exchange rides ring_attention /
            ulysses_attention (meta_parallel/ring_attention.py)

and their degrees. `build_mesh()` materializes the jax Mesh and raises
on a host with fewer devices than the config asks for: a step that was
asked to shard never runs unsharded in silence.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

#: canonical axis order of the partitioner mesh (sep only materializes
#: when its degree > 1 — a trailing size-1 axis is harmless but noisy)
AXIS_NAMES = ("data", "fsdp", "tp", "sep")


@dataclass(frozen=True)
class MeshConfig:
    """Declarative pod-scale sharding config (see module doc).

    `rules` maps logical param/activation axis names to mesh axes
    (None = replicated, tuple = sharded over several axes); None picks
    `rules.DEFAULT_RULES`. `batch_axes` is where activation batch dims
    land; `stream_seq_axis` names the mesh axis the residual stream's
    sequence dim is sharded over BETWEEN blocks (Megatron-SP style;
    None = auto: `sep` when sep > 1, else `tp`)."""

    data: int = 1
    fsdp: int = 1
    tp: int = 1
    sep: int = 1
    rules: tuple | None = None
    batch_axes: tuple = ("data", "fsdp")
    stream_seq_axis: str | None = None
    #: mesh axes whose collectives cross the data-center network instead
    #: of ICI (the ROADMAP hybrid-mesh split: dp over DCN, everything
    #: else intra-slice). The static cost model charges these axes at
    #: FLAGS_analysis_dcn_gbps / _dcn_alpha_us.
    dcn_axes: tuple = ()

    def __post_init__(self):
        for name in AXIS_NAMES:
            if int(getattr(self, name)) < 1:
                raise ValueError(
                    f"MeshConfig.{name} must be >= 1, got "
                    f"{getattr(self, name)}")
        bad = [a for a in self.batch_axes if a not in AXIS_NAMES]
        if bad:
            raise ValueError(
                f"MeshConfig.batch_axes names unknown mesh axes {bad} "
                f"(known: {AXIS_NAMES})")
        bad = [a for a in self.dcn_axes if a not in AXIS_NAMES]
        if bad:
            raise ValueError(
                f"MeshConfig.dcn_axes names unknown mesh axes {bad} "
                f"(known: {AXIS_NAMES})")
        if self.stream_seq_axis is not None \
                and self.stream_seq_axis not in AXIS_NAMES:
            raise ValueError(
                f"MeshConfig.stream_seq_axis {self.stream_seq_axis!r} is "
                f"not a mesh axis (known: {AXIS_NAMES})")

    # ------------------------------------------------------------ shape
    @property
    def axis_names(self) -> tuple:
        return ("data", "fsdp", "tp") + (("sep",) if self.sep > 1 else ())

    @property
    def axis_sizes(self) -> dict:
        return {n: int(getattr(self, n)) for n in self.axis_names}

    @property
    def num_devices(self) -> int:
        return int(np.prod(list(self.axis_sizes.values())))

    def fabric(self, axis: str) -> str:
        """Which interconnect a collective over `axis` rides: "dcn" when
        the config maps the axis across hosts, else "ici"."""
        return "dcn" if axis in self.dcn_axes else "ici"

    @property
    def seq_axis(self) -> str:
        """Mesh axis the stream's sequence dim is sharded over between
        blocks: the explicit override, else sep when context parallel is
        on, else tp (the Megatron sequence-parallel placement the
        hand-wired sp_utils path uses for `mp`)."""
        if self.stream_seq_axis is not None:
            return self.stream_seq_axis
        return "sep" if self.sep > 1 else "tp"

    def describe(self) -> str:
        return "x".join(f"{n}{s}" for n, s in self.axis_sizes.items())

    # ------------------------------------------------------------ mesh
    def build_mesh(self):
        """The jax Mesh this config names. Raises when the host exposes
        fewer devices than the config needs."""
        import jax
        from jax.sharding import Mesh

        devs = jax.devices()
        need = self.num_devices
        if need > len(devs):
            raise ValueError(
                f"MeshConfig {self.describe()} needs {need} devices, "
                f"{len(devs)} visible — shrink the config or force a "
                "virtual platform (--xla_force_host_platform_device_count)")
        dims = [self.axis_sizes[n] for n in self.axis_names]
        return Mesh(np.array(devs[:need]).reshape(dims), self.axis_names)

    # ------------------------------------------------------ serialization
    def to_dict(self) -> dict:
        d = {"axes": self.axis_sizes}
        if self.dcn_axes:
            d["dcn_axes"] = list(self.dcn_axes)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "MeshConfig":
        axes = dict(d.get("axes", d))
        kw = {k: int(v) for k, v in axes.items() if k in AXIS_NAMES}
        if isinstance(d, dict) and d.get("dcn_axes"):
            kw["dcn_axes"] = tuple(d["dcn_axes"])
        return cls(**kw)
