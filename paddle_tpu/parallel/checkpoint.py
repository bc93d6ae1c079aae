"""Sharding-aware checkpoint/resume for the jax-native training path.

Reference capability: save/load_persistables (io.py:501,769) and the
distributed-aware save that reassembles pserver-resident shards
(io.py:320). The Program path already has those (paddle_tpu.io); THIS
module covers the flagship jax-native path (parallel/train.py
TrainState): parameters + optimizer moments may be sharded over the
mesh (ZeRO-1), and a checkpoint must round-trip those shardings. Orbax
is the TPU-native serialization engine — each host writes its own
shards (the multi-host story for free), and restore lays arrays out
directly into the target NamedShardings.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax

from .train import TrainState

# leaf-dtype manifest written next to every orbax payload: restore
# compares it against the template's dtypes so a checkpoint written
# under one precision policy can never SILENTLY restore into another
# width — it either casts explicitly (cast_dtypes=True) or fails with
# the mismatch list. Pre-manifest checkpoints restore as before.
DTYPES_FILE = "_DTYPES.json"

# mesh manifest written next to the payload: world size + axis sizes of
# the mesh the state was sharded over at save time. Restore compares it
# against the template's mesh to detect a CROSS-WORLD-SIZE restore (a
# mesh-4 checkpoint onto a mesh-3 job after elastic scale-in) — orbax
# lays shards out into the template's NamedShardings either way, but
# the reshard is surfaced as a `restore_resharded` event + elastic
# resharding metrics, and genuinely incompatible layouts (leaf shapes
# that differ) are refused with ReshardError before orbax dies with an
# opaque per-array error. Pre-manifest checkpoints restore as before.
MESH_FILE = "_MESH.json"


class PrecisionMismatchError(ValueError):
    """Checkpoint leaf dtypes disagree with the restore template's —
    e.g. a bf16-policy checkpoint restored into an f32-policy run.
    Re-restore with cast_dtypes=True to convert explicitly, or rebuild
    the template under the checkpoint's policy."""


class ReshardError(ValueError):
    """Checkpoint cannot be resharded onto the restore template: leaf
    global SHAPES disagree (a different model, layer width, or a
    world-size-dependent layout), as opposed to the same logical arrays
    merely sharded over a different mesh — that case reshards fine.
    Raised by `restore_train_state` / `reshard_train_state` so an
    elastic resize fails loudly instead of restoring garbage."""


def _payload(state: TrainState) -> Dict:
    payload = {"params": state.params, "opt_state": state.opt_state,
               "step": state.step}
    if getattr(state, "loss_scale", None) is not None:
        # dynamic loss-scaling state (mixed-precision policies) rides
        # the same orbax payload, so CheckpointManager round-trips it
        payload["loss_scale"] = state.loss_scale
    return payload


def _dtype_manifest(tree) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        dt = getattr(leaf, "dtype", None)
        if dt is not None:
            out[jax.tree_util.keystr(path)] = str(dt)
    return out


def _tree_mesh(tree):
    """The Mesh the first NamedSharding-carrying leaf lives on, or
    None for host-only trees (numpy payload tests)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        sh = getattr(leaf, "sharding", None)
        mesh = getattr(sh, "mesh", None)
        if mesh is not None and getattr(mesh, "devices", None) is not None:
            return mesh
    return None


def _mesh_manifest(tree) -> Optional[Dict]:
    mesh = _tree_mesh(tree)
    if mesh is None:
        return None
    return {"world_size": int(mesh.devices.size),
            "axes": {str(a): int(s)
                     for a, s in dict(mesh.shape).items()}}


def save_train_state(path: str, state: TrainState, force: bool = False):
    """Write {params, opt_state, step[, loss_scale]} with their
    shardings to `path`, plus a leaf-dtype manifest (_DTYPES.json) that
    restore uses to refuse silent cross-precision restores.

    force=False refuses to overwrite an existing checkpoint: orbax
    deletes the old directory BEFORE the new write commits, so
    overwriting in place would leave zero restorable checkpoints if the
    process dies mid-save. Periodic savers should write step-stamped
    dirs (`root/step_N`, see latest_step_dir) and prune old ones only
    after the new save returns."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    payload = _payload(state)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, payload, force=force)
    from ..observability import events as _events
    from ..resilience.atomic import json_dump

    json_dump(_dtype_manifest(payload), os.path.join(path, DTYPES_FILE))
    mesh_meta = _mesh_manifest(payload)
    if mesh_meta is not None:
        json_dump(mesh_meta, os.path.join(path, MESH_FILE))
    _events.emit("checkpoint", site="save_train_state", dir=path,
                 step=int(state.step))


def restore_train_state(path: str, template: TrainState,
                        cast_dtypes: bool = False) -> TrainState:
    """Restore into the TEMPLATE's structure and shardings — pass a
    freshly-built `init_state(params)` result; its (possibly ZeRO-1
    sharded) layout tells orbax where every shard of every array lands.

    Precision safety: when the checkpoint carries a dtype manifest and
    any leaf width disagrees with the template (a bf16 checkpoint into
    an f32-policy template, or vice versa), the restore FAILS with a
    PrecisionMismatchError listing the offenders — restoring across
    widths silently would corrupt the run's numerics story. Pass
    cast_dtypes=True to reshard dtypes explicitly instead: leaves are
    read back at their SAVED dtype and cast to the template's.

    The same contract covers STRUCTURE: dynamic loss-scaling state
    exists only under mixed policies, so a checkpoint and template
    disagreeing on its presence is also a cross-precision restore —
    it fails with PrecisionMismatchError, or under cast_dtypes=True
    reshards explicitly (template-side loss-scale state keeps its
    fresh init; checkpoint-side state is read and dropped)."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    target = {"params": template.params,
              "opt_state": template.opt_state,
              "step": template.step}
    if getattr(template, "loss_scale", None) is not None:
        target["loss_scale"] = template.loss_scale

    saved_dtypes: Optional[Dict[str, str]] = None
    manifest_path = os.path.join(path, DTYPES_FILE)
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            saved_dtypes = json.load(f)

    # cross-world-size detection: a mesh manifest that disagrees with
    # the template's mesh means this restore is an elastic RESHARD —
    # refuse incompatible layouts up front, surface the reshard in
    # events/metrics, and let orbax lay the shards out into the
    # template's shardings (the actual data movement).
    saved_mesh: Optional[Dict] = None
    mesh_path = os.path.join(path, MESH_FILE)
    if os.path.exists(mesh_path):
        with open(mesh_path) as f:
            saved_mesh = json.load(f)
    tmpl_mesh = _mesh_manifest(target)
    resharding = (saved_mesh is not None and tmpl_mesh is not None
                  and saved_mesh != tmpl_mesh)
    if resharding:
        _check_reshardable(path, target)
    import time as _time

    t0 = _time.perf_counter()

    # structure guard BEFORE the per-leaf dtype loop (which only sees
    # keys present on both sides): loss-scale presence differing would
    # otherwise die inside orbax with an opaque tree-structure error
    # that cast_dtypes could never fix. Manifest-less checkpoints
    # predate loss-scale payloads, so no manifest == no saved state.
    tmpl_has_ls = "loss_scale" in target
    saved_has_ls = (saved_dtypes is not None
                    and any(k.startswith("['loss_scale']")
                            for k in saved_dtypes))
    drop_saved_ls = False
    if saved_has_ls != tmpl_has_ls:
        if not cast_dtypes:
            side = ("the checkpoint carries dynamic loss-scaling state "
                    "but the restore template has none"
                    if saved_has_ls else
                    "the restore template expects dynamic loss-scaling "
                    "state but the checkpoint has none")
            raise PrecisionMismatchError(
                f"checkpoint at {path} was written under a different "
                f"precision policy than the restore template ({side}). "
                f"Restore with cast_dtypes=True to reshard explicitly "
                f"— the template's fresh loss-scale state is kept, a "
                f"checkpoint-side one is dropped — or rebuild the "
                f"template under the checkpoint's policy.")
        if tmpl_has_ls:
            # f32-era checkpoint into a mixed template: restore the
            # shared items; the template keeps its fresh loss scale
            target.pop("loss_scale")

        else:
            drop_saved_ls = True

    mismatches = []
    if saved_dtypes is not None:
        for key, want in _dtype_manifest(target).items():
            have = saved_dtypes.get(key)
            if have is not None and have != want:
                mismatches.append((key, have, want))
        if mismatches and not cast_dtypes:
            head = ", ".join(f"{k}: checkpoint {h} vs template {w}"
                             for k, h, w in mismatches[:8])
            raise PrecisionMismatchError(
                f"checkpoint at {path} was written under a different "
                f"precision than the restore template ({len(mismatches)}"
                f" leaf dtype mismatches: {head}"
                f"{', ...' if len(mismatches) > 8 else ''}). Restore "
                f"with cast_dtypes=True to convert explicitly, or "
                f"rebuild the template under the checkpoint's policy.")

    mismatch_keys = {k for k, _, _ in mismatches}

    def leaf_abstract(kpath, x):
        if not hasattr(x, "sharding"):
            return x
        dtype = x.dtype
        key = jax.tree_util.keystr(kpath)
        if key in mismatch_keys:
            # explicit dtype reshard: read at the SAVED width (the
            # bytes on disk), cast to the template width afterwards
            import numpy as np

            dtype = np.dtype(saved_dtypes[key])
        return jax.ShapeDtypeStruct(x.shape, dtype, sharding=x.sharding)

    abstract = jax.tree_util.tree_map_with_path(leaf_abstract, target)
    with ocp.StandardCheckpointer() as ckptr:
        if drop_saved_ls:
            # orbax demands an exact top-level structure match, so the
            # checkpoint-only loss_scale item must appear in the
            # abstract tree — shape/dtype come from the checkpoint's
            # own metadata; the restored values are dropped below
            import numpy as np

            abstract["loss_scale"] = jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(
                    tuple(m.shape), np.dtype(str(m.dtype))),
                _saved_tree_metadata(ckptr, path)["loss_scale"])
        restored = ckptr.restore(path, abstract)
    if drop_saved_ls:
        restored.pop("loss_scale", None)
    if mismatch_keys:
        def recast(kpath, saved, tmpl):
            if jax.tree_util.keystr(kpath) in mismatch_keys:
                return jax.device_put(saved.astype(tmpl.dtype),
                                      tmpl.sharding)
            return saved

        restored = jax.tree_util.tree_map_with_path(
            lambda p, s, t: recast(p, s, t), restored, target)
    loss_scale = restored.get("loss_scale")
    if tmpl_has_ls and loss_scale is None:
        # explicit cross-precision reshard into a mixed template: the
        # checkpoint had no loss-scale state, keep the fresh init
        loss_scale = template.loss_scale
    if resharding:
        from ..distributed.rendezvous import RESHARD_SECONDS
        from ..observability import events as _events

        seconds = _time.perf_counter() - t0
        RESHARD_SECONDS.observe(seconds)
        _events.emit("restore_resharded", dir=path,
                     from_world=saved_mesh["world_size"],
                     to_world=tmpl_mesh["world_size"],
                     from_axes=saved_mesh["axes"],
                     to_axes=tmpl_mesh["axes"],
                     seconds=round(seconds, 6))
    return TrainState(restored["params"], restored["opt_state"],
                      restored["step"], loss_scale)


def _saved_tree_metadata(ckptr, path: str) -> dict:
    """The saved pytree's per-leaf ArrayMetadata (shape, dtype), as the
    installed orbax (0.11) hands it out: StepMetadata -> item_metadata
    (TreeMetadata) -> tree."""
    return ckptr.metadata(path).item_metadata.tree


def _check_reshardable(path: str, target) -> None:
    """Refusal path for cross-mesh restores: every leaf's GLOBAL shape
    in the checkpoint must match the template's. Sharding may differ
    arbitrarily (that's the reshard); shapes may not — a shape mismatch
    means a different model or a world-size-dependent layout, and orbax
    would otherwise fail per-array with no layout diagnosis."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        meta = _saved_tree_metadata(ckptr, path)
    bad = []
    tgt_leaves = {jax.tree_util.keystr(p): l for p, l in
                  jax.tree_util.tree_flatten_with_path(target)[0]}
    for p, m in jax.tree_util.tree_flatten_with_path(dict(meta))[0]:
        key = jax.tree_util.keystr(p)
        tl = tgt_leaves.get(key)
        if tl is None or not hasattr(tl, "shape") \
                or not hasattr(m, "shape"):
            continue
        if tuple(m.shape) != tuple(tl.shape):
            bad.append((key, tuple(m.shape), tuple(tl.shape)))
    if bad:
        head = ", ".join(f"{k}: checkpoint {s} vs template {t}"
                         for k, s, t in bad[:8])
        raise ReshardError(
            f"checkpoint at {path} cannot be resharded onto this "
            f"template: {len(bad)} leaf shape mismatches ({head}"
            f"{', ...' if len(bad) > 8 else ''}) — resharding moves "
            f"the SAME logical arrays onto a different mesh; it cannot "
            f"reconcile different shapes")


def reshard_train_state(state: TrainState, template: TrainState) -> TrainState:
    """In-process cross-mesh reshard: lay every leaf of `state` out on
    `template`'s shardings (per-leaf `jax.device_put`; a transfer the
    runtime refuses — e.g. source buffers on devices the new mesh no
    longer includes — falls back to gather-to-host + re-put). The
    no-checkpoint-round-trip path for an elastic resize when the state
    is already in memory; the checkpoint path is `restore_train_state`
    with a template built on the new mesh. Values are moved, never
    recomputed — leaves stay bit-identical. Shape disagreements raise
    ReshardError (same refusal contract as the checkpoint path)."""
    import numpy as np

    from ..distributed.rendezvous import RESHARD_SECONDS
    import time as _time

    t0 = _time.perf_counter()

    def move(kpath, leaf, tleaf):
        sh = getattr(tleaf, "sharding", None)
        if sh is None:
            return leaf
        if hasattr(leaf, "shape") and tuple(leaf.shape) != tuple(tleaf.shape):
            raise ReshardError(
                f"cannot reshard leaf {jax.tree_util.keystr(kpath)}: "
                f"state shape {tuple(leaf.shape)} vs template "
                f"{tuple(tleaf.shape)}")
        try:
            return jax.device_put(leaf, sh)
        except Exception:  # lint-exempt:swallow: jax raises several types for cross-mesh puts; gather fallback below is the contract
            return jax.device_put(np.asarray(leaf), sh)

    out = jax.tree_util.tree_map_with_path(move, state, template)
    RESHARD_SECONDS.observe(_time.perf_counter() - t0)
    return out


def latest_step_dir(root: str, committed_only: bool = False) -> Optional[str]:
    """Resume helper: `root/step_N` directories -> the highest-N path.

    CAUTION: with committed_only=False (the legacy default) this returns
    the highest-numbered directory even if it is a PARTIAL write left by
    a process that died mid-save. committed_only=True only counts
    directories carrying resilience.CheckpointManager's commit marker;
    for managed checkpoints prefer `CheckpointManager.restore_latest`,
    which additionally falls back past corrupt-but-committed dirs."""
    if not os.path.isdir(root):
        return None
    if committed_only:
        from ..resilience.checkpoint_manager import CheckpointManager

        return CheckpointManager(root).latest_committed_dir()
    best, best_n = None, -1
    for d in os.listdir(root):
        if d.startswith("step_") and os.path.isdir(os.path.join(root, d)):
            try:
                n = int(d.split("_", 1)[1])
            except ValueError:
                continue
            if n > best_n:
                best, best_n = os.path.join(root, d), n
    return best
