"""Minimal dependency-free checkpointing: pytrees -> flat npz + tree spec.

Saves model params, server-optimizer state, and sampler state (the K-Vib
cumulative feedback omega is part of the training state — a restarted server
must not forget what it learned about clients).

Layout:  <dir>/<name>.npz          flat arrays keyed by index, plus
                                   ``dtypes``: every leaf's dtype name
         <dir>/<name>.treedef.txt  str(jax.tree_util.tree_structure)

npz keeps only the width of the dtypes numpy does not define itself
(bfloat16, the float8 family — ``ml_dtypes``), so those leaves are stored as
same-width unsigned bit patterns and viewed back by the recorded name.
Both files are published atomically (tmp + ``os.replace``) so a crash mid-save
can never leave a half-written file under the final name.  Restore requires a
template pytree with matching structure (the standard "abstract state"
pattern); the saved treedef string, every leaf's shape, AND every leaf's dtype
are validated against the template — a mismatch raises instead of silently
casting, because a dtype drift between writer and reader is a config drift,
not a convertible format difference.

Step-numbered checkpoints, manifests, retention, and ``latest()`` discovery
live one level up in ``repro.checkpoint.manager.CheckpointManager``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = ["save_checkpoint", "restore_checkpoint"]


def _sidecar_path(fname: str) -> str:
    return fname[: -len(".npz")] + ".treedef.txt"


def _npz_keeps(dtype) -> bool:
    """Whether an npz round trip returns ``dtype`` itself."""
    fmt = np.lib.format
    try:
        return fmt.descr_to_dtype(fmt.dtype_to_descr(dtype)) == dtype
    except (TypeError, ValueError):
        return False


def save_checkpoint(path: str, state) -> str:
    """Write `state` (any pytree of arrays) to `<path>.npz`. Returns the file."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with obs.span("ckpt.fetch", leaves=len(leaves)):
        leaves = [np.asarray(x) for x in leaves]
    arrays = {
        f"leaf_{i}": x if _npz_keeps(x.dtype) else x.view(f"u{x.dtype.itemsize}")
        for i, x in enumerate(leaves)
    }
    arrays["dtypes"] = np.array([x.dtype.name for x in leaves], dtype=str)
    fname = path if path.endswith(".npz") else path + ".npz"
    sidecar = _sidecar_path(fname)
    # Stage BOTH files before publishing EITHER: a crash can leave stale tmp
    # files but never a half-written .npz or .treedef.txt under its final name.
    with obs.span("ckpt.write"):
        tmp = fname + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        tmp_sidecar = sidecar + ".tmp"
        with open(tmp_sidecar, "w") as f:
            f.write(str(treedef))
        os.replace(tmp, fname)  # atomic publish
        os.replace(tmp_sidecar, sidecar)  # atomic publish
    return fname


def restore_checkpoint(path: str, template):
    """Restore into the structure of `template`.

    Validates the saved treedef string against the template's and every
    leaf's shape and dtype — any mismatch raises ``ValueError`` (dtypes are
    NOT silently cast; see module docstring).
    """
    fname = path if path.endswith(".npz") else path + ".npz"
    leaves_t, treedef = jax.tree_util.tree_flatten(template)
    with open(_sidecar_path(fname)) as f:
        saved_treedef = f.read()
    if saved_treedef != str(treedef):
        raise ValueError(
            "checkpoint treedef does not match template structure:\n"
            f"  saved:    {saved_treedef}\n  template: {treedef}"
        )
    with np.load(fname) as data:
        names = data["dtypes"] if "dtypes" in data.files else None
        n = len(data.files) - (names is not None)
        if n != len(leaves_t):
            raise ValueError(
                f"checkpoint has {n} leaves, template has {len(leaves_t)}"
            )
        leaves = []
        for i, t in enumerate(leaves_t):
            arr = data[f"leaf_{i}"]
            if names is not None and arr.dtype.name != str(names[i]):
                arr = arr.view(jnp.dtype(str(names[i])))
            t_arr = np.asarray(t)
            if arr.shape != t_arr.shape:
                raise ValueError(
                    f"leaf {i}: checkpoint shape {arr.shape} != template {t_arr.shape}"
                )
            if arr.dtype != t_arr.dtype:
                raise ValueError(
                    f"leaf {i}: checkpoint dtype {arr.dtype} != template "
                    f"{t_arr.dtype} (refusing to cast silently)"
                )
            leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
