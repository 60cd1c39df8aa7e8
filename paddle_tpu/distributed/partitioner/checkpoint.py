"""Resharding-aware train-state checkpoints for partitioned runs.

Save rides `ckpt` manifest v2: every mesh-sharded leaf commits PER
ADDRESSABLE SHARD keyed by ``Shard.index`` (each host writes only what
it holds — no gathered global array), and the manifest records the mesh
axis sizes + PartitionSpec per leaf. Restore reassembles the global
arrays on host, applies the ordinary bitwise train-state restore, then
RE-PLACES the parameters under whatever MeshConfig the restoring run
declares — a data4×tp2 checkpoint restores onto data2×tp4, onto a
different fsdp degree, or onto one device, because placement is a
property of the RESTORING config, not of the bytes. The atomic-commit /
async-saver / retry / fault-injection machinery is `ckpt.core`'s,
untouched.

Backward compat: a v1 manifest (pre-partitioner) carries no per-leaf
sharding — it restores exactly as before and the result names the
reason (``"manifest_v1_replicated"``) instead of silently pretending it
was sharded.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .api import shard_model
from .mesh import MeshConfig


@dataclass
class PartitionedRestore:
    """Result of :func:`restore_partitioned`."""

    step: int
    data: dict
    directory: str
    #: per-leaf {"mesh", "spec"} recorded at save time ({} for v1)
    saved_shardings: dict
    #: why the restored placement is what it is: "resharded" (v2 ckpt
    #: re-placed under the restoring config), "replicated" (no config
    #: given), or "manifest_v1_replicated" (pre-v2 checkpoint: nothing
    #: recorded to reshard FROM — restored replicated, then placed)
    reason: str = "resharded"
    plan: object = None
    fallbacks: list = field(default_factory=list)


def save_partitioned(root, step, model=None, optimizer=None, config=None,
                     data_state=None, extra=None, **save_kwargs) -> dict:
    """Capture the full train state (ckpt.capture_train_state: params,
    optimizer slots, both RNG streams, data position) and commit it
    SHARDED — sub-shard files keyed by Shard.index, mesh+spec in the
    manifest. `config` only stamps the fingerprint; the shardings
    recorded are whatever the leaves actually carry."""
    from ... import ckpt

    tree = ckpt.capture_train_state(model, optimizer, step=step,
                                    data_state=data_state, extra=extra)
    fp = dict(save_kwargs.pop("fingerprint_extra", None) or {})
    if config is not None:
        fp["mesh_config"] = config.describe()
    return ckpt.save_checkpoint(root, step, tree, sharded=True,
                                fingerprint_extra=fp or None,
                                **save_kwargs)


def restore_partitioned(root, model=None, optimizer=None, config=None,
                        step=None, restore_rng=True) -> PartitionedRestore:
    """Restore the newest verifying checkpoint and RE-PLACE the model
    under `config` (resharding-on-restore). With config=None the state
    restores replicated (single-device semantics). Returns the plan of
    the new placement so callers can audit what moved."""
    from ... import ckpt

    r = ckpt.restore_checkpoint(root, step=step)
    meta = ckpt.restore_train_state(r.tree, model, optimizer,
                                    restore_rng=restore_rng)
    info = ckpt.manifest_shardings(r.manifest)
    plan = None
    if config is not None and model is not None:
        # set_value swapped replicated host buffers into the params;
        # placement is re-derived from the RESTORING config — this IS
        # the reshard (v2's recorded specs are provenance, not a
        # constraint on where the bytes may live next)
        plan = shard_model(model, config)
    if info["version"] < 2:
        reason = "manifest_v1_replicated"
    elif plan is not None:
        reason = "resharded"
    else:
        reason = "replicated"
    return PartitionedRestore(step=int(meta["step"]), data=meta["data"],
                              directory=r.directory,
                              saved_shardings=info["leaves"],
                              reason=reason, plan=plan,
                              fallbacks=list(r.fallbacks))
