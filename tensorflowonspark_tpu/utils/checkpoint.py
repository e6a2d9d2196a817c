"""Checkpoint / export utilities.

Parity intent: the reference delegates checkpointing to TF and contributes
the *contract* — model_dir plumbing, chief-only SavedModel export with
non-chief no-op (reference compat.py:10-17), grace-period export after
feeding stops.  Here:

- ``save_checkpoint``/``load_checkpoint``: a dependency-free npz format
  for plain pytrees (always available, used by CI tests);
- ``export_model``: the chief-only export gate;
- ``async_checkpointer``: orbax-backed async checkpointing for real runs
  (GCS-capable), import-gated;
- blessing manifests (``bless_checkpoint``/``verify_manifest``/
  ``tombstone_checkpoint``): the deployment loop's integrity contract
  (workloads/deploy_loop.py, docs/deployment.md).  No reference
  counterpart — the reference hands checkpoints to TF Serving unsigned
  and unverified (SURVEY §1 L7); here a promoted checkpoint carries
  per-file sha256 digests + the eval score that gated it, and restore
  paths skip tombstoned/corrupt steps instead of crashing on them.
"""

from __future__ import annotations

import hashlib
import io
import json
import logging
import os
import time

import numpy as np

from tensorflowonspark_tpu.recordio import fs as _fs
from tensorflowonspark_tpu.utils import faults, metrics_registry, telemetry

logger = logging.getLogger(__name__)


# numpy's own file format knows only numpy's own types: an extension type
# (bfloat16, float8: ml_dtypes) is stored as the unsigned integers of its
# width under ``<key>::<dtype name>`` and viewed back at load
_EXT_SEP = "::"


def _storable(key, arr):
    arr = np.asarray(arr)
    if arr.dtype.kind != "V" or arr.dtype.names is not None:
        return key, arr
    return (f"{key}{_EXT_SEP}{arr.dtype.name}",
            arr.view(np.dtype(f"u{arr.dtype.itemsize}")))


def _restored(key, arr):
    if _EXT_SEP not in key:
        return key, arr
    import ml_dtypes

    key, name = key.rsplit(_EXT_SEP, 1)
    return key, arr.view(np.dtype(getattr(ml_dtypes, name)))


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    else:
        key, arr = _storable(prefix[:-1], tree)
        out[key] = arr
    return out


def _unflatten(flat):
    tree = {}
    for key, value in flat.items():
        key, value = _restored(key, value)
        parts = key.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return tree


def save_checkpoint(ckpt_dir, params, step, keep=3):
    """Write step-stamped npz checkpoint to any filesystem (local,
    gs://, hdfs://, ... via fsspec); prune old ones."""
    t0 = time.perf_counter()
    with telemetry.span("checkpoint/save", step=step):
        faults.check("checkpoint.save", step=step)
        _fs.makedirs(ckpt_dir)
        flat = _flatten(_to_host(params))
        path = _fs.join(ckpt_dir, f"ckpt-{step:08d}.npz")
        if _fs.is_local(ckpt_dir):
            lp = _fs.local_path(path)
            # pid-unique tmp: concurrent writers (several workers sharing
            # one filesystem) must not clobber each other's in-flight file
            tmp = f"{lp}.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                np.savez(f, **flat)
            os.replace(tmp, lp)  # atomic publish
        else:
            buf = io.BytesIO()  # object stores publish atomically on PUT
            np.savez(buf, **flat)
            _fs.write_bytes(path, buf.getvalue())
        logger.info("saved checkpoint %s", path)
        ckpts = sorted(
            p for p in _fs.listdir(ckpt_dir)
            if p.startswith("ckpt-") and p.endswith(".npz")
        )
        for old in ckpts[:-keep]:
            _fs.remove(_fs.join(ckpt_dir, old))
        metrics_registry.inc("tfos_checkpoint_saves_total")
        metrics_registry.observe("tfos_checkpoint_save_ms",
                                 (time.perf_counter() - t0) * 1000.0)
        return path


def latest_checkpoint(ckpt_dir):
    """Path of the newest *restorable* npz checkpoint, or None.

    Integrity-hardened (deploy-loop satellite): steps that are
    tombstoned, fail their blessing manifest, or are visibly truncated
    are skipped with a warning and the previous step wins — a torn
    write must cost one checkpoint interval, not the whole resume."""
    for step in sorted(_steps_by_format(ckpt_dir)["npz"], reverse=True):
        ok, reason = _restorable(ckpt_dir, step, "npz")
        if ok:
            return _fs.join(ckpt_dir, f"ckpt-{step:08d}.npz")
        logger.warning("skipping checkpoint step %d: %s", step, reason)
    return None


def load_checkpoint(path):
    t0 = time.perf_counter()
    with telemetry.span("checkpoint/restore", path=os.path.basename(path)):
        with _fs.open_file(path, "rb") as f, np.load(f) as z:
            out = _unflatten({k: z[k] for k in z.files})
        metrics_registry.inc("tfos_checkpoint_restores_total")
        metrics_registry.observe("tfos_checkpoint_restore_ms",
                                 (time.perf_counter() - t0) * 1000.0)
        return out


def export_model(export_dir, params, ctx=None, metadata=None):
    """Chief-only model export (parity: reference compat.py:10-17 —
    non-chief workers write nothing instead of a dummy dir)."""
    if ctx is not None and not is_chief(ctx):
        logger.info("export_model: not chief (%s:%s), skipping",
                    ctx.job_name, ctx.task_index)
        return None
    with telemetry.span("checkpoint/export"):
        _fs.makedirs(export_dir)
        flat = _flatten(_to_host(params))
        # straight into the file: a second copy of ten gigabytes of
        # weights in a BytesIO does not fit beside the first
        with _fs.open_file(_fs.join(export_dir, "params.npz"), "wb") as f:
            np.savez(f, **flat)
        meta = {"format": "tfos-tpu-export-v1"}
        meta.update(metadata or {})
        _fs.write_bytes(_fs.join(export_dir, "export.json"),
                        json.dumps(meta).encode())
        logger.info("exported model to %s", export_dir)
        return export_dir


def load_exported(export_dir):
    with _fs.open_file(_fs.join(export_dir, "params.npz"), "rb") as f, \
            np.load(f) as z:
        params = _unflatten({k: z[k] for k in z.files})
    return params, load_export_meta(export_dir)


def load_export_meta(export_dir):
    """Export metadata alone, no params read: the elastic adopt path
    (serving/elastic.py) resolves the predict symbol from it while the
    params arrive live from a surviving replica."""
    return json.loads(_fs.read_bytes(_fs.join(export_dir, "export.json")))


def is_chief(ctx):
    """process 0 duties: chief/master role, else worker:0
    (reference ctx.job_name=='chief' convention)."""
    if ctx.job_name in ("chief", "master"):
        return True
    has_chief = any(j in ctx.cluster_spec for j in ("chief", "master"))
    return not has_chief and ctx.job_name == "worker" and ctx.task_index == 0


def _to_host(params):
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x), params)


def pack_pytree(tree):
    """Arbitrary pytree (optax states, namedtuples, ...) -> flat
    {index: ndarray} dict storable by save_checkpoint (npz holds flat
    arrays; the structure is re-imposed by unpack_pytree at load)."""
    import jax

    return {
        f"{i:05d}": np.asarray(x)
        for i, x in enumerate(jax.tree_util.tree_leaves(tree))
    }


def unpack_pytree(flat, like):
    """Rebuild a pytree with the structure of ``like`` from pack_pytree
    output (leaf order is jax's canonical tree order)."""
    import jax

    leaves = [flat[k] for k in sorted(flat)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), leaves
    )


def step_of(ckpt_path):
    """Step number encoded in a ``ckpt-<step>.npz`` path."""
    name = os.path.basename(ckpt_path)
    return int(name[len("ckpt-"):-len(".npz")])


# --------------------------------------------------------------------------
# Blessing manifests (deployment-loop integrity contract).
#
# A manifest is one JSON file ``bless-<step>.json`` next to the checkpoint
# it covers: per-file sha256 + byte count, the step, and the eval score
# that gated promotion.  ``verify_manifest`` re-digests the files; a
# ``tombstone`` entry quarantines a checkpoint that regressed in canary
# (workloads/deploy_loop.py rollback path) so no restore path — trainer
# resume, serving reload, elastic adopt — ever picks it again.

MANIFEST_FORMAT = "tfos-bless-v1"


def manifest_path(ckpt_dir, step):
    return _fs.join(ckpt_dir, f"bless-{step:08d}.json")


def _step_files(ckpt_dir, step):
    """Relative paths of every file making up checkpoint ``step``
    (the npz file, or the orbax digit-dir walked recursively)."""
    names = []
    npz = f"ckpt-{step:08d}.npz"
    if _fs.exists(_fs.join(ckpt_dir, npz)):
        names.append(npz)
    odir = _fs.join(ckpt_dir, str(step))
    if _fs.isdir(odir):
        if _fs.is_local(odir):
            root = _fs.local_path(odir)
            for dirpath, _dirs, files in os.walk(root):
                rel = os.path.relpath(dirpath, _fs.local_path(ckpt_dir))
                names.extend(os.path.join(rel, f) for f in sorted(files))
        else:
            names.extend(f"{step}/{n}" for n in sorted(_fs.listdir(odir))
                         if not n.endswith("/"))
    return names


def _digest(path):
    """(sha256-hex, byte count) of one checkpoint file, streamed."""
    h = hashlib.sha256()
    n = 0
    with _fs.open_file(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            h.update(chunk)
            n += len(chunk)
    return h.hexdigest(), n


def _write_manifest(ckpt_dir, step, manifest):
    blob = json.dumps(manifest, sort_keys=True).encode()
    path = manifest_path(ckpt_dir, step)
    if _fs.is_local(ckpt_dir):
        lp = _fs.local_path(path)
        tmp = f"{lp}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, lp)  # atomic publish, same as save_checkpoint
    else:
        _fs.write_bytes(path, blob)
    return path


def bless_checkpoint(ckpt_dir, step, score=None, eval_metrics=None):
    """Write the integrity manifest that marks ``step`` *blessed*.

    Called by the promotion controller after the eval gate passes:
    digests every file of the checkpoint so later restores can prove
    the bytes they read are the bytes that were evaluated.  Returns the
    manifest path.  Raises ``FileNotFoundError`` when the step has no
    files — blessing nothing must fail loudly."""
    files = _step_files(ckpt_dir, step)
    if not files:
        raise FileNotFoundError(
            f"bless_checkpoint: no checkpoint files for step {step} "
            f"in {ckpt_dir}")
    manifest = {
        "format": MANIFEST_FORMAT,
        "step": int(step),
        "score": None if score is None else float(score),
        "eval": dict(eval_metrics or {}),
        "files": {},
        "blessed_ts": time.time(),
        "tombstone": None,
    }
    for rel in files:
        digest, nbytes = _digest(_fs.join(ckpt_dir, rel))
        manifest["files"][rel.replace(os.sep, "/")] = {
            "sha256": digest, "bytes": nbytes}
    path = _write_manifest(ckpt_dir, step, manifest)
    telemetry.event(telemetry.DEPLOY_BLESS, step=int(step),
                    score=manifest["score"], files=len(files))
    metrics_registry.set_gauge("tfos_deploy_blessed_step", int(step))
    logger.info("blessed checkpoint step %d (%d files) -> %s",
                step, len(files), path)
    return path


def read_manifest(ckpt_dir, step):
    """Parsed manifest dict for ``step``, or None (absent/unparseable)."""
    path = manifest_path(ckpt_dir, step)
    if not _fs.exists(path):
        return None
    try:
        manifest = json.loads(_fs.read_bytes(path))
    except (OSError, ValueError) as e:
        logger.warning("unreadable manifest %s: %s", path, e)
        return None
    return manifest if isinstance(manifest, dict) else None


def verify_manifest(ckpt_dir, step):
    """(ok, reason) for the blessing manifest of ``step``.

    ``(False, "unblessed")`` when no manifest exists — the caller
    decides whether blessing is required (serving reload) or optional
    (trainer resume, see :func:`restore_any`)."""
    manifest = read_manifest(ckpt_dir, step)
    if manifest is None:
        return False, "unblessed"
    if manifest.get("tombstone"):
        reason = (manifest["tombstone"] or {}).get("reason", "")
        return False, f"tombstoned ({reason})"
    files = manifest.get("files") or {}
    if not files:
        return False, "empty manifest"
    for rel, info in sorted(files.items()):
        path = _fs.join(ckpt_dir, rel)
        if not _fs.exists(path):
            return False, f"missing file {rel}"
        try:
            digest, nbytes = _digest(path)
        except OSError as e:
            return False, f"unreadable file {rel}: {e}"
        if nbytes != info.get("bytes"):
            return False, (f"size mismatch {rel}: "
                           f"{nbytes} != {info.get('bytes')}")
        if digest != info.get("sha256"):
            return False, f"digest mismatch {rel}"
    return True, "ok"


def tombstone_checkpoint(ckpt_dir, step, reason):
    """Quarantine ``step``: mark its manifest (created if absent) with a
    tombstone so every restore path skips it.  The rollback half of the
    deployment loop — a checkpoint that regressed in canary must never
    be served, resumed from, or adopted by a regrown replica again."""
    manifest = read_manifest(ckpt_dir, step) or {
        "format": MANIFEST_FORMAT, "step": int(step), "score": None,
        "eval": {}, "files": {}, "blessed_ts": None,
    }
    manifest["tombstone"] = {"reason": str(reason), "ts": time.time()}
    path = _write_manifest(ckpt_dir, step, manifest)
    metrics_registry.inc("tfos_deploy_tombstones_total")
    logger.warning("tombstoned checkpoint step %d: %s", step, reason)
    return path


def blessed_steps(ckpt_dir):
    """Sorted steps with a live (non-tombstoned) blessing manifest."""
    if not _fs.isdir(ckpt_dir):
        return []
    steps = []
    for name in _fs.listdir(ckpt_dir):
        name = name.rstrip("/")
        if not (name.startswith("bless-") and name.endswith(".json")):
            continue
        try:
            step = int(name[len("bless-"):-len(".json")])
        except ValueError:
            continue
        manifest = read_manifest(ckpt_dir, step)
        if manifest is not None and not manifest.get("tombstone"):
            steps.append(step)
    return sorted(steps)


def latest_blessed(ckpt_dir):
    """(step, path) of the newest blessed checkpoint whose manifest
    verifies, or (None, None).  The rollback target resolver."""
    for step in sorted(blessed_steps(ckpt_dir), reverse=True):
        ok, reason = verify_manifest(ckpt_dir, step)
        if not ok:
            logger.warning("blessed step %d fails verify: %s", step, reason)
            continue
        npz = _fs.join(ckpt_dir, f"ckpt-{step:08d}.npz")
        if _fs.exists(npz):
            return step, npz
        return step, _fs.join(ckpt_dir, str(step))
    return None, None


def _npz_intact(path):
    """Cheap truncation check: an npz is a zip, and truncation destroys
    the central directory at the tail.  Local paths only (remote reads
    would defeat 'cheap'); non-local returns True and the load attempt
    is the arbiter."""
    if not _fs.is_local(path):
        return True
    import zipfile

    try:
        with zipfile.ZipFile(_fs.local_path(path)) as z:
            z.namelist()
        return True
    except Exception:  # noqa: BLE001 - any unzip failure means torn
        return False


def _restorable(ckpt_dir, step, fmt, blessed_only=False):
    """(ok, reason): should a restore path attempt ``step``?

    Manifest-present steps must verify (tombstones and digest drift are
    hard skips); manifest-absent steps pass unless ``blessed_only``
    (serving reloads demand blessing, trainer resume does not).  npz
    steps additionally get the cheap truncation probe."""
    manifest = read_manifest(ckpt_dir, step)
    if manifest is not None:
        ok, reason = verify_manifest(ckpt_dir, step)
        if not ok:
            return False, reason
    elif blessed_only:
        return False, "unblessed"
    if fmt == "npz":
        path = _fs.join(ckpt_dir, f"ckpt-{step:08d}.npz")
        if not _fs.exists(path):
            return False, "missing npz"
        if not _npz_intact(path):
            return False, "truncated npz"
    return True, "ok"


def restore_step(ckpt_dir, step):
    """Params tree of checkpoint ``step`` exactly, whichever format holds
    it.  The pinned-reload path: canary replicas load the candidate,
    rollback re-pins the blessed step (serving/replicas.py
    ``_maybe_reload``)."""
    npz = _fs.join(ckpt_dir, f"ckpt-{step:08d}.npz")
    if _fs.exists(npz):
        return load_checkpoint(npz)
    if _fs.isdir(_fs.join(ckpt_dir, str(step))):
        ckpt = AsyncCheckpointer(ckpt_dir)
        try:
            return ckpt.restore_at(step)
        finally:
            ckpt.close()
    raise FileNotFoundError(
        f"restore_step: no checkpoint for step {step} in {ckpt_dir}")


def restore_latest(ckpt_dir):
    """(params, step) from the newest restorable checkpoint, or (None, 0).

    The resume half of the recovery contract (SURVEY.md §5: recovery is
    "restart job from checkpoint"): training mains call this at startup
    and begin from the returned step.  Hardened like
    :func:`latest_checkpoint`: a torn/tombstoned newest step falls back
    to the previous one with a warning.
    """
    for step in sorted(_steps_by_format(ckpt_dir)["npz"], reverse=True):
        ok, reason = _restorable(ckpt_dir, step, "npz")
        if not ok:
            logger.warning("skipping checkpoint step %d: %s", step, reason)
            continue
        path = _fs.join(ckpt_dir, f"ckpt-{step:08d}.npz")
        try:
            tree = load_checkpoint(path)
        except Exception as e:  # noqa: BLE001 - torn file past the probe
            logger.warning("checkpoint %s unreadable: %s", path, e)
            continue
        logger.info("resuming from %s", path)
        return tree, step
    return None, 0


def _steps_by_format(ckpt_dir):
    """{'npz': [steps...], 'orbax': [steps...]} found in ``ckpt_dir``.

    npz checkpoints are ``ckpt-<step>.npz`` files; orbax CheckpointManager
    step dirs are all-digit directory names.  Listing is format-blind so
    auto-resume works whichever writer the dead incarnation used."""
    out = {"npz": [], "orbax": []}
    if not _fs.isdir(ckpt_dir):
        return out
    for name in _fs.listdir(ckpt_dir):
        name = name.rstrip("/")
        if name.startswith("ckpt-") and name.endswith(".npz"):
            try:
                out["npz"].append(step_of(name))
            except ValueError:
                pass
        elif name.isdigit():
            out["orbax"].append(int(name))
    return out


def latest_step(ckpt_dir):
    """Newest checkpoint step in ``ckpt_dir`` across BOTH formats (npz
    and orbax), or None when the dir is absent/empty."""
    steps = _steps_by_format(ckpt_dir)
    every = steps["npz"] + steps["orbax"]
    return max(every) if every else None


def latest(ckpt_dir):
    """(step, path) of the newest checkpoint across BOTH formats, or
    (None, None).  The serving hot-reload watcher
    (serving/replicas.ReplicaPool) polls this cheaply — it is a listing,
    never a restore; ``restore_any`` does the actual load."""
    steps = _steps_by_format(ckpt_dir)
    best_npz = max(steps["npz"]) if steps["npz"] else -1
    best_orbax = max(steps["orbax"]) if steps["orbax"] else -1
    if best_orbax < 0 and best_npz < 0:
        return None, None
    if best_orbax >= best_npz:
        return best_orbax, _fs.join(ckpt_dir, str(best_orbax))
    return best_npz, _fs.join(ckpt_dir, f"ckpt-{best_npz:08d}.npz")


def restore_any(ckpt_dir, target_shardings=None, blessed_only=False):
    """(tree, step) from the newest restorable checkpoint regardless of
    format, or (None, 0).  The auto-resume entry point (``TFNodeContext
    .restore_latest``): a relaunched node must continue from whatever its
    dead predecessor last published, whether it saved via
    ``save_checkpoint`` (npz) or :class:`AsyncCheckpointer` (orbax).

    Candidates are tried newest-first; steps that are tombstoned, fail
    their blessing manifest, are truncated, or raise on load are skipped
    with a warning and the previous step is tried (deploy-loop
    satellite: a bad newest checkpoint costs one interval, not the
    resume).  ``blessed_only=True`` additionally requires a verified
    blessing manifest — the serving-reload contract (only promoted
    checkpoints may serve traffic).

    Without ``target_shardings`` leaves restore as host numpy with NO
    placement contract — fine for single-device resumes, wrong for a
    mesh.  ``target_shardings`` makes placement explicit (the reshard
    step of elastic recovery, docs/elastic.md): a pytree of ``Sharding``
    matching the restored tree, or a callable ``tree -> shardings``
    derived from the restored structure (e.g. ``lambda t:
    fsdp_sharding(mesh, t)``).  The checkpoint may have been written
    under a DIFFERENT mesh shape: restore is host-side either way, so
    re-placement works across topologies (``elastic/reshard.py``)."""
    steps = _steps_by_format(ckpt_dir)
    # newest first; orbax wins a step tie (matches the historical
    # best_orbax >= best_npz preference)
    cands = sorted(
        [(s, "npz") for s in steps["npz"]]
        + [(s, "orbax") for s in steps["orbax"]],
        key=lambda c: (c[0], c[1] == "orbax"), reverse=True)
    tree, step = None, 0
    for s, fmt in cands:
        ok, reason = _restorable(ckpt_dir, s, fmt, blessed_only=blessed_only)
        if not ok:
            logger.warning("skipping checkpoint step %d (%s): %s",
                           s, fmt, reason)
            continue
        try:
            if fmt == "npz":
                tree = load_checkpoint(
                    _fs.join(ckpt_dir, f"ckpt-{s:08d}.npz"))
            else:
                ckpt = AsyncCheckpointer(ckpt_dir)
                try:
                    tree = ckpt.restore_at(s)
                finally:
                    ckpt.close()
            step = s
            break
        except Exception as e:  # noqa: BLE001 - torn past the probe
            logger.warning("checkpoint step %d (%s) unreadable: %s",
                           s, fmt, e)
            tree = None
    if tree is None:
        return None, 0
    if target_shardings is not None:
        # function import: the elastic package re-exports reshard() the
        # function over the reshard module attribute
        from tensorflowonspark_tpu.elastic.reshard import reshard

        tree = reshard(tree, target_shardings)
    return tree, step


class AsyncCheckpointer:
    """Orbax-backed async checkpointing (GCS-capable) behind the same
    save/restore contract as the npz functions: device-to-host copy and
    serialization overlap training instead of blocking the step loop.

    Usage::

        ckpt = AsyncCheckpointer(model_dir)
        params, start = ckpt.restore_latest()
        for step in range(start, steps):
            ...
            if step % save_every == 0:
                ckpt.save(step, params)   # returns immediately
        ckpt.close()                      # waits for in-flight saves
    """

    def __init__(self, ckpt_dir, keep=3):
        import orbax.checkpoint as ocp

        # URLs (gs://...) go to orbax/tensorstore verbatim; only plain
        # local paths are absolutized (os.path.abspath would mangle a URL)
        if _fs.is_local(ckpt_dir):
            ckpt_dir = os.path.abspath(_fs.local_path(ckpt_dir))
        self._ocp = ocp
        self._mngr = ocp.CheckpointManager(
            ckpt_dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep, enable_async_checkpointing=True
            ),
        )

    def save(self, step, tree):
        """Queue an async save of ``tree`` at ``step`` (non-blocking)."""
        import jax

        faults.check("checkpoint.save", step=step)

        # orbax's StandardSave rejects numpy scalar leaves (np.float32);
        # promote them to 0-d arrays, which round-trip identically
        tree = jax.tree.map(
            lambda x: np.asarray(x) if isinstance(x, np.generic) else x,
            tree)
        self._mngr.save(step, args=self._ocp.args.StandardSave(tree))

    def latest_step(self):
        return self._mngr.latest_step()

    def restore_latest(self):
        """(tree, next_step) — (None, 0) when no checkpoint exists."""
        step = self._mngr.latest_step()
        if step is None:
            return None, 0
        # explicit StandardRestore: a fresh manager over an existing dir
        # has no registered handler yet and raises KeyError without it
        return self._mngr.restore(
            step, args=self._ocp.args.StandardRestore()), step

    def restore_at(self, step):
        """Tree of one specific step (the pinned-reload/rollback path)."""
        return self._mngr.restore(
            step, args=self._ocp.args.StandardRestore())

    def wait(self):
        self._mngr.wait_until_finished()

    def close(self):
        self._mngr.wait_until_finished()
        self._mngr.close()


def async_checkpointer(ckpt_dir, keep=3):
    """Back-compat constructor for :class:`AsyncCheckpointer`."""
    return AsyncCheckpointer(ckpt_dir, keep=keep)
