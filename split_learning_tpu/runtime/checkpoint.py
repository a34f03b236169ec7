"""Checkpoint / resume.

Parity with the reference's single-file whole-model checkpoints
(``/root/reference/src/Server.py:190-193`` save after every successful
round; ``:230-256`` load + shard-extract at round start; delete the file
to reset, README.md:173-177).  Here the full param pytree (+ batch stats +
round counter) is written with orbax; shard extraction is
:func:`~split_learning_tpu.models.split.shard_params` pytree slicing —
the dict-key matching the reference does by hand.

Checkpoints are named ``{MODEL}_{DATASET}`` under the configured
checkpoint root (the reference's ``{model}_{data}.pth`` naming).  A
msgpack fallback (flax.serialization) covers environments where orbax is
unusable; load auto-detects the format.

Crash atomicity: a save never touches the live checkpoint.  The tree is
written to a hidden slot directory (``.{name}.data0``/``.data1``,
alternating) and published by atomically replacing the ``{name}``
symlink (``os.replace`` of a fresh symlink — one rename syscall).  A
process killed at ANY point leaves either the previous complete
checkpoint or the new complete checkpoint visible, never a torn one;
:func:`load_checkpoint` additionally treats an unreadable/truncated
checkpoint as absent (warn + ``None``) instead of raising, so a corrupt
file can never block a restart.
"""

from __future__ import annotations

import contextlib
import ctypes
import json
import os
import pathlib
import shutil
import warnings
from typing import Any

import jax
import numpy as np

try:
    import orbax.checkpoint as ocp
    _HAVE_ORBAX = True
except Exception:  # pragma: no cover - orbax is baked into the image
    _HAVE_ORBAX = False


def _to_host(tree: Any) -> Any:
    return jax.tree_util.tree_map(np.asarray, tree)


def checkpoint_path(directory: str | pathlib.Path,
                    model_key: str) -> pathlib.Path:
    return pathlib.Path(directory).resolve() / model_key


def _write_tree(target: pathlib.Path, tree: Any) -> None:
    if _HAVE_ORBAX:
        ocp.PyTreeCheckpointer().save(target, tree, force=True)
    else:  # pragma: no cover
        import flax.serialization
        target.mkdir(parents=True, exist_ok=True)
        (target / "state.msgpack").write_bytes(
            flax.serialization.to_bytes(tree))


def _publish(path: pathlib.Path, slot_name: str) -> None:
    """Atomically point the live ``path`` symlink at ``slot_name``."""
    staged = path.parent / f".{path.name}.lnk"
    try:
        staged.unlink()
    except FileNotFoundError:
        pass
    os.symlink(slot_name, staged)
    if path.exists() and not path.is_symlink():
        # legacy real-directory layout: one-time migration (the only
        # non-atomic window this scheme ever has)
        shutil.rmtree(path)
    os.replace(staged, path)


def save_checkpoint(directory: str | pathlib.Path, model_key: str,
                    params: Any, batch_stats: Any | None = None,
                    round_idx: int = 0, extra: dict | None = None,
                    tracer=None) -> None:
    """``tracer`` (``runtime/spans.py``), when given, journals the two
    halves of the save: ``ckpt_pull`` (device to host) and
    ``ckpt_store`` (the write and the symlink flip)."""
    def span(name):
        return (tracer.span(name) if tracer is not None
                else contextlib.nullcontext())

    path = checkpoint_path(directory, model_key)
    path.parent.mkdir(parents=True, exist_ok=True)
    with span("ckpt_pull"):
        tree = {"params": _to_host(params),
                "batch_stats": _to_host(batch_stats or {}),
                "meta": {"round_idx": np.int64(round_idx)}}
    with span("ckpt_store"):
        _store(path, model_key, tree)
    if extra:
        meta = path.parent / f"{model_key}.meta.json"
        staged = path.parent / f".{model_key}.meta.json.tmp"
        staged.write_text(json.dumps(extra))
        os.replace(staged, meta)


def _store(path: pathlib.Path, model_key: str, tree: Any) -> None:
    # write into the slot NOT currently live, then flip the symlink —
    # the previous checkpoint stays intact until the new one is complete
    live = os.readlink(path) if path.is_symlink() else None
    slot_name = (f".{model_key}.data1"
                 if live == f".{model_key}.data0"
                 else f".{model_key}.data0")
    tmp = path.parent / f".{model_key}.tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    _write_tree(tmp, tree)
    final = path.parent / slot_name
    shutil.rmtree(final, ignore_errors=True)
    os.replace(tmp, final)
    _publish(path, slot_name)


def load_checkpoint(directory: str | pathlib.Path,
                    model_key: str) -> dict | None:
    """Returns {params, batch_stats, round_idx}, or None when the
    checkpoint is absent OR unreadable (torn write from a hard crash,
    bit rot): a corrupt checkpoint warns and is treated as no
    checkpoint rather than wedging the restart."""
    path = checkpoint_path(directory, model_key)
    if not path.exists():   # dangling symlink also reads as absent
        return None
    try:
        if (path / "state.msgpack").exists():  # pragma: no cover
            import flax.serialization
            tree = flax.serialization.msgpack_restore(
                (path / "state.msgpack").read_bytes())
        elif _HAVE_ORBAX:
            tree = ocp.PyTreeCheckpointer().restore(path)
        else:  # pragma: no cover
            return None
        return {"params": tree["params"],
                "batch_stats": tree.get("batch_stats") or {},
                "round_idx": int(tree["meta"]["round_idx"])}
    except Exception as e:  # noqa: BLE001 — any torn/corrupt state
        warnings.warn(
            f"checkpoint at {path} is unreadable ({type(e).__name__}: "
            f"{e}); ignoring it and starting fresh", RuntimeWarning,
            stacklevel=2)
        return None


def release_freed_memory() -> None:
    """Hand the heap's free pages back to the system (glibc's
    ``malloc_trim``; a no-op where there is none).  A save or a restore
    leaves about twice the tree's size in freed serialization buffers, and
    a process whose allocator keeps what it frees carries them, with what
    compiling left, for the rest of its life: a 2.27 GB tree's job met a
    40 GiB machine's limit that way (PERF.md section 6, PR 34).  30 ms a
    gigabyte released; called once a training run, after its last
    checkpoint is durable, never inside a round."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def save_sidecar_arrays(directory: str | pathlib.Path, name: str,
                        arrays: dict[str, Any]) -> None:
    """Atomically persist a small named-array sidecar (e.g. a client's
    wire-codec error-feedback residuals, ``runtime/codec/sparse.py``):
    write ``.{name}.npz.tmp`` then one ``os.replace`` — the same
    crash-atomicity contract as the model checkpoint, without the slot
    machinery (a sidecar is one small file)."""
    root = pathlib.Path(directory).resolve()
    root.mkdir(parents=True, exist_ok=True)
    tmp = root / f".{name}.npz.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **{k: np.asarray(v) for k, v in arrays.items()})
    os.replace(tmp, root / f"{name}.npz")


def load_sidecar_arrays(directory: str | pathlib.Path,
                        name: str) -> dict | None:
    """Sidecar arrays, or None when absent OR unreadable (torn write:
    warn and treat as absent, mirroring :func:`load_checkpoint`)."""
    path = pathlib.Path(directory).resolve() / f"{name}.npz"
    if not path.exists():
        return None
    try:
        with np.load(path, allow_pickle=False) as z:
            return {k: z[k] for k in z.files}
    except Exception as e:  # noqa: BLE001 — any torn/corrupt state
        warnings.warn(
            f"sidecar at {path} is unreadable ({type(e).__name__}: "
            f"{e}); ignoring it", RuntimeWarning, stacklevel=2)
        return None


def delete_checkpoint(directory: str | pathlib.Path,
                      model_key: str) -> None:
    """Reference's "delete the .pth to reset" (README.md:173-177)."""
    path = checkpoint_path(directory, model_key)
    if path.is_symlink():
        path.unlink()
    elif path.exists():
        shutil.rmtree(path)
    for p in path.parent.glob(f".{model_key}.*"):
        # slot dirs, tmp dir, staged links
        if p.is_dir() and not p.is_symlink():
            shutil.rmtree(p, ignore_errors=True)
        else:
            try:
                p.unlink()
            except OSError as e:
                # a leftover slot means the NEXT save may publish into
                # a dirty directory — surface it instead of silence
                warnings.warn(f"could not remove checkpoint debris "
                              f"{p}: {e}", RuntimeWarning, stacklevel=2)
