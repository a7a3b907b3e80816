"""Checkpoint save/load.

Parity with the reference's per-pass parameter dumps
(trainer/ParamUtil.cpp:80 saveParameters → save_dir/pass-%05d/) and the Go
pserver checkpoints that additionally persist optimizer state with integrity
checks (go/pserver/service.go:146 parameterCheckpoint, CRC + atomic write).

Format: one .npz per pytree (params / states / opt) + manifest.json with
shapes, dtypes and a CRC of each file; writes are atomic (tmp + rename).

Async mode (the zero-stall checkpoint path): `AsyncCheckpointer` owns ONE
background writer thread; `save_pass_async` flattens the (already
host-resident) trees on the caller's thread and hands the npz/CRC/v1-format/
manifest/retention work to the writer, double-buffered so at most one
snapshot is in flight. `wait()` is the durability barrier — the trainer
invokes it on train() exit, before load(), and in the preemption drain, so a
checkpoint path handed to a supervisor always names a completed write."""

from __future__ import annotations

import json
import logging
import os
import shutil
import tempfile
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from paddle_tpu.core import faults

log = logging.getLogger("paddle_tpu.checkpoint")

LATEST_FILE = "latest"


def _path_key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _to_numpy_tree(tree: Any) -> Dict[str, np.ndarray]:
    flat = {}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves:
        flat[_path_key(path)] = np.asarray(leaf)
    return flat


def tree_shape_mismatches(
    template: Any, flat: Dict[str, np.ndarray]
) -> List[Tuple[str, Tuple[int, ...], Tuple[int, ...]]]:
    """(key, expected_shape, found_shape) for every `flat` entry whose shape
    disagrees with the matching `template` leaf. restore_tree silently keeps
    the template value for those — callers that must NOT lose state (the
    trainer's optimizer resume) turn a non-empty result into a hard error
    naming expected vs found shard counts instead of resuming with silently
    re-initialized slots."""
    out: List[Tuple[str, Tuple[int, ...], Tuple[int, ...]]] = []
    leaves, _ = jax.tree_util.tree_flatten_with_path(template)
    for path, leaf in leaves:
        key = _path_key(path)
        if key in flat and tuple(np.shape(flat[key])) != tuple(np.shape(leaf)):
            out.append((key, tuple(np.shape(leaf)), tuple(np.shape(flat[key]))))
    return out


def tree_missing_keys(template: Any, flat: Dict[str, np.ndarray]) -> List[str]:
    """Template leaf paths with NO entry in `flat` at all. restore_tree
    keeps the template's (freshly initialized) value for those — for state
    that must round-trip exactly (the trainer's optimizer slots), a missing
    key is the same silent wrong resume as a shape mismatch, just invisible
    to tree_shape_mismatches (which only compares keys present in both)."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(template)
    return [
        key for path, _leaf in leaves
        if (key := _path_key(path)) not in flat
    ]


def restore_tree(template: Any, flat: Dict[str, np.ndarray]) -> Any:
    """Rebuild a pytree shaped like `template` from a flat path→array dict
    (inverse of _to_numpy_tree). Leaves missing from `flat` or with mismatched
    shapes keep the template's value."""
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    new_leaves = []
    for path, leaf in leaves:
        key = _path_key(path)
        if key in flat and tuple(np.shape(flat[key])) == tuple(np.shape(leaf)):
            new_leaves.append(jnp.asarray(flat[key], dtype=leaf.dtype))
        else:
            new_leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _save_npz_atomic(path: str, arrays: Dict[str, np.ndarray]) -> int:
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    # suffix must be .npz: np.savez appends it to any other filename
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp.npz")
    os.close(fd)
    try:
        np.savez(tmp, **arrays)
        with open(tmp, "rb") as f:
            crc = zlib.crc32(f.read())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return crc


def save_pass(
    save_dir: str,
    pass_id: int,
    params: Dict[str, Any],
    states: Optional[Dict[str, Any]] = None,
    opt_state: Optional[Any] = None,
    extra_meta: Optional[Dict[str, Any]] = None,
    v1_binary: bool = True,
    keep_last_n: Optional[int] = None,
) -> str:
    """Write save_dir/pass-%05d/{params,states,opt}.npz + manifest.json, then
    point save_dir/latest at it (tmp+rename, so the pointer is never torn).

    v1_binary (default on) additionally writes each parameter as a
    reference-format `Parameter::save` file in the pass dir (ParamUtil layout
    — SURVEY §7 step 8 model interchange; see trainer/v1_format.py), so every
    pass dir doubles as a reference-consumable model dir.

    keep_last_n (None/0 = keep all): after a successful write, delete the
    oldest pass dirs beyond the newest N — never the one just written. The
    dir is renamed aside first, so a reader never sees a half-deleted pass."""
    flats = _flatten_pass_trees(params, states, opt_state)
    return _write_pass_files(
        save_dir, pass_id, flats, extra_meta, v1_binary, keep_last_n
    )


def _flatten_pass_trees(
    params: Dict[str, Any],
    states: Optional[Dict[str, Any]],
    opt_state: Optional[Any],
) -> Dict[str, Dict[str, np.ndarray]]:
    """Flatten the three checkpoint trees to {name: {path: ndarray}} — the
    only step of a save that must see the caller's (possibly device) arrays;
    everything after it is pure file I/O."""
    flats: Dict[str, Dict[str, np.ndarray]] = {}
    for name, tree in [("params", params), ("states", states), ("opt", opt_state)]:
        if tree is None or (isinstance(tree, dict) and not tree):
            continue
        flats[name] = _to_numpy_tree(tree)
    return flats


def _write_pass_files(
    save_dir: str,
    pass_id: int,
    flats: Dict[str, Dict[str, np.ndarray]],
    extra_meta: Optional[Dict[str, Any]],
    v1_binary: bool,
    keep_last_n: Optional[int],
) -> str:
    """The file-I/O body of save_pass, runnable on an AsyncCheckpointer
    writer thread: npz + CRC + v1-format + manifest + latest pointer +
    retention. Input arrays must already be host numpy."""
    if keep_last_n is not None and keep_last_n < 0:
        raise ValueError(f"keep_last_n must be >= 0, got {keep_last_n}")
    pdir = os.path.join(save_dir, f"pass-{pass_id:05d}")
    os.makedirs(pdir, exist_ok=True)
    if v1_binary and "params" in flats:
        from paddle_tpu.trainer import v1_format

        v1_format.save_model_dir(pdir, flats["params"])
    manifest: Dict[str, Any] = {"pass_id": pass_id, "files": {}, "version": 1}
    if extra_meta:
        manifest["extra"] = extra_meta
    for name, flat in flats.items():
        path = os.path.join(pdir, f"{name}.npz")
        crc = _save_npz_atomic(path, flat)
        if faults.get().fire("ckpt_truncate"):
            # chaos hook: a torn write that defeated tmp+rename (lying fs,
            # power cut after rename) — CRC verification must catch it
            with open(path, "r+b") as f:
                f.truncate(max(os.path.getsize(path) // 2, 1))
        manifest["files"][name] = {
            "crc32": crc,
            "keys": {k: [list(v.shape), str(v.dtype)] for k, v in flat.items()},
        }
    mpath = os.path.join(pdir, "manifest.json")
    fd, tmp = tempfile.mkstemp(dir=pdir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, mpath)
    _write_latest(save_dir, pass_id)
    if keep_last_n:
        _prune_old_passes(save_dir, keep=keep_last_n, just_written=pdir)
    return pdir


class AsyncCheckpointer:
    """Single background writer for zero-stall checkpointing.

    Double-buffered: at most one snapshot is in flight; submitting a second
    blocks (before any new work starts) until the first lands. A writer
    failure is remembered and re-raised on the NEXT submit()/wait() so disk
    errors surface on the training thread instead of dying silently with a
    daemon thread. The thread is a daemon on purpose: a kill mid-write is
    exactly the torn-write case the manifest CRCs exist to catch."""

    def __init__(self, name: str = "paddle-tpu-ckpt-writer"):
        self._cond = threading.Condition()
        self._job: Optional[Tuple[Callable[[], Any], str]] = None
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._name = name

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, daemon=True, name=self._name
            )
            self._thread.start()

    def _run(self) -> None:
        while True:
            with self._cond:
                while self._job is None:
                    self._cond.wait()
                fn, desc = self._job
            err: Optional[BaseException] = None
            try:
                fn()
            except BaseException as e:  # surfaces at the next submit()/wait()
                err = e
                log.error("async checkpoint write (%s) failed: %s", desc, e)
            with self._cond:
                if err is not None:
                    self._error = err
                self._job = None
                self._cond.notify_all()

    @property
    def in_flight(self) -> bool:
        with self._cond:
            return self._job is not None

    def submit(self, fn: Callable[[], Any], desc: str = "checkpoint") -> None:
        """Queue one write job; blocks while a previous one is in flight."""
        self._ensure_thread()
        with self._cond:
            while self._job is not None:
                self._cond.wait()
            self._raise_pending_locked()
            self._job = (fn, desc)
            self._cond.notify_all()

    def wait(self) -> None:
        """Durability barrier: returns once no write is in flight, re-raising
        the writer's exception (once) if the last write failed."""
        with self._cond:
            while self._job is not None:
                self._cond.wait()
            self._raise_pending_locked()

    def _raise_pending_locked(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err


def save_pass_async(
    writer: AsyncCheckpointer,
    save_dir: str,
    pass_id: int,
    params: Dict[str, Any],
    states: Optional[Dict[str, Any]] = None,
    opt_state: Optional[Any] = None,
    extra_meta: Optional[Dict[str, Any]] = None,
    v1_binary: bool = True,
    keep_last_n: Optional[int] = None,
) -> str:
    """save_pass, minus the stall: trees are flattened on the calling thread
    (pass host-resident numpy trees — the trainer pre-fetches device arrays
    with copy_to_host_async), all file I/O happens on `writer`'s thread.
    Returns the pass dir path that is durable once writer.wait() returns."""
    if keep_last_n is not None and keep_last_n < 0:
        raise ValueError(f"keep_last_n must be >= 0, got {keep_last_n}")
    flats = _flatten_pass_trees(params, states, opt_state)
    pdir = os.path.join(save_dir, f"pass-{pass_id:05d}")
    writer.submit(
        lambda: _write_pass_files(
            save_dir, pass_id, flats, extra_meta, v1_binary, keep_last_n
        ),
        desc=pdir,
    )
    return pdir


def _write_latest(save_dir: str, pass_id: int) -> None:
    fd, tmp = tempfile.mkstemp(dir=save_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        f.write(f"pass-{pass_id:05d}\n")
    os.replace(tmp, os.path.join(save_dir, LATEST_FILE))


def _list_pass_ids(save_dir: str) -> List[int]:
    try:
        names = os.listdir(save_dir)
    except OSError:
        return []
    out = []
    for d in names:
        if d.startswith("pass-") and os.path.isdir(os.path.join(save_dir, d)):
            try:
                out.append(int(d.split("-")[1]))
            except ValueError:
                continue
    return sorted(out)


def _prune_old_passes(save_dir: str, keep: int, just_written: str) -> None:
    # sweep trash left by a crash between rename-aside and rmtree in an
    # earlier run, or keep_last_n's disk bound erodes one dir per kill
    for d in os.listdir(save_dir):
        if d.startswith(".trash-pass-"):
            shutil.rmtree(os.path.join(save_dir, d), ignore_errors=True)
    passes = _list_pass_ids(save_dir)
    for pid in passes[:-keep] if keep < len(passes) else []:
        victim = os.path.join(save_dir, f"pass-{pid:05d}")
        if os.path.abspath(victim) == os.path.abspath(just_written):
            continue
        # rename aside first so a concurrent reader never opens a
        # half-deleted pass dir; the rmtree then races with nobody
        trash = os.path.join(save_dir, f".trash-pass-{pid:05d}")
        try:
            os.replace(victim, trash)
        except OSError as e:
            log.warning("checkpoint retention: cannot retire %s: %s", victim, e)
            continue
        shutil.rmtree(trash, ignore_errors=True)


def verify_pass(pdir: str) -> bool:
    """True when `pdir` holds a readable manifest and every file it lists
    exists and passes its CRC — the load_pass acceptance test, minus the
    loading."""
    mpath = os.path.join(pdir, "manifest.json")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        for name in manifest.get("files", {}):
            path = os.path.join(pdir, f"{name}.npz")
            with open(path, "rb") as f:
                if zlib.crc32(f.read()) != manifest["files"][name]["crc32"]:
                    return False
    except (OSError, ValueError, KeyError):
        return False
    return True


def find_latest_valid_pass(save_dir: str) -> Optional[int]:
    """Newest pass id under `save_dir` whose checkpoint passes CRC, or None.

    Tries the `latest` pointer first, then scans pass dirs newest-to-oldest;
    corrupt or partial pass dirs (torn npz, missing manifest — a crash
    mid-save) are skipped with a warning, so auto-resume lands on the newest
    checkpoint that can actually be trusted."""
    if not os.path.isdir(save_dir):
        return None
    candidates = _list_pass_ids(save_dir)[::-1]
    try:
        with open(os.path.join(save_dir, LATEST_FILE)) as f:
            pointed = int(f.read().strip().split("-")[1])
        candidates = [pointed] + [p for p in candidates if p != pointed]
    except (OSError, ValueError, IndexError):
        pass
    for pid in candidates:
        pdir = os.path.join(save_dir, f"pass-{pid:05d}")
        if verify_pass(pdir):
            return pid
        log.warning(
            "auto-resume: skipping corrupt/partial checkpoint %s "
            "(CRC or manifest check failed)", pdir,
        )
    return None


def pass_manifest(save_dir: str, pass_id: int) -> Dict[str, Any]:
    """The manifest of one pass dir, or {} — how auto-resume learns whether a
    checkpoint is a preemption-drain mid-pass save (extra.mid_pass +
    extra.batches_done) or a normal pass-boundary one."""
    try:
        with open(
            os.path.join(save_dir, f"pass-{pass_id:05d}", "manifest.json")
        ) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def is_v1_model_dir(dirname: str) -> bool:
    """True when `dirname` looks like a reference ParamUtil model directory:
    no manifest.json, and at least one regular file whose 16 leading bytes
    parse as a `Parameter::Header` (Parameter.h:263) consistent with the
    file's length (16 + 4*size bytes)."""
    from paddle_tpu.trainer import v1_format

    if not os.path.isdir(dirname) or os.path.exists(
        os.path.join(dirname, "manifest.json")
    ):
        return False
    for fn in os.listdir(dirname):
        path = os.path.join(dirname, fn)
        if not os.path.isfile(path):
            continue
        try:
            with open(path, "rb") as f:
                raw = f.read(v1_format.HEADER.size)
            if len(raw) != v1_format.HEADER.size:
                continue
            fmt, value_size, size = v1_format.HEADER.unpack(raw)
            if (
                fmt == v1_format.PARAM_FORMAT_ORIGINAL
                and value_size == 4
                and os.path.getsize(path) == v1_format.HEADER.size + 4 * size
            ):
                return True
        except OSError:
            continue
    return False


def load_pass(
    save_dir: str,
    pass_id: Optional[int] = None,
    params_template: Union[None, Dict[str, Any], Callable[[], Dict[str, Any]]] = None,
) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray], Dict[str, np.ndarray], Dict]:
    """Load (params, states, opt_flat, manifest). pass_id=None → latest.
    `params_template` may be a zero-arg callable, resolved only if the v1
    branch needs it.

    Accepts three on-disk layouts, sniffed in order:
    - save_dir/pass-%05d/ with manifest.json (this repo's native format);
    - save_dir itself is a pass dir (manifest.json directly inside);
    - save_dir (or save_dir/pass-%05d) is a reference ParamUtil model
      directory of raw `Parameter::save` files (paddle/trainer/ParamUtil.cpp:50
      loadParameters) — needs `params_template` for shapes; conv filters are
      transposed from the reference flat [cin,kh,kw,cout] layout to HWIO by
      v1_format.read_param. Optimizer state/states are absent in that case
      (the reference checkpoints values only)."""
    v1_sniffed = False
    if os.path.exists(os.path.join(save_dir, "manifest.json")):
        pdir = save_dir
    elif pass_id is None and is_v1_model_dir(save_dir):
        pdir = save_dir
        v1_sniffed = True
    else:
        if pass_id is None:
            passes = sorted(
                int(d.split("-")[1])
                for d in os.listdir(save_dir)
                if d.startswith("pass-") and os.path.isdir(os.path.join(save_dir, d))
            )
            if not passes:
                raise FileNotFoundError(f"no pass-* checkpoints under {save_dir}")
            pass_id = passes[-1]
        pdir = os.path.join(save_dir, f"pass-{pass_id:05d}")
    if not os.path.exists(os.path.join(pdir, "manifest.json")) and (
        v1_sniffed or is_v1_model_dir(pdir)
    ):
        if callable(params_template):
            # lazy template: only the v1 branch needs the shapes, and
            # building them may be non-trivial (a zero3 trainer gathers its
            # flat-sharded params to canonical) — resolve it only here
            params_template = params_template()
        if params_template is None:
            raise ValueError(
                f"{pdir!r} is a reference-format (v1 binary) model dir; loading "
                "it needs a params_template for shapes — init the trainer state "
                "first (Trainer.load does this automatically)"
            )
        from paddle_tpu.trainer import v1_format

        params = v1_format.load_model_dir(pdir, _to_numpy_tree(params_template))
        return params, {}, {}, {"pass_id": pass_id, "v1_binary": True, "files": {}}
    with open(os.path.join(pdir, "manifest.json")) as f:
        manifest = json.load(f)
    out = {}
    for name in ("params", "states", "opt"):
        path = os.path.join(pdir, f"{name}.npz")
        if name in manifest["files"] and os.path.exists(path):
            with open(path, "rb") as f:
                crc = zlib.crc32(f.read())
            if crc != manifest["files"][name]["crc32"]:
                raise IOError(f"checkpoint {path} failed CRC check")
            with np.load(path) as z:
                out[name] = {k: z[k] for k in z.files}
        else:
            out[name] = {}
    return out["params"], out["states"], out["opt"], manifest
