"""Step-numbered checkpoint management with an atomic JSON manifest.

``CheckpointManager`` turns the flat ``save_checkpoint``/``restore_checkpoint``
pair into a preemption-safe subsystem for the segmented compiled horizon
(``repro.fed.state.run_segmented``): every segment boundary publishes a
step-numbered checkpoint, the manifest write is the atomic commit point, and
a restarted process discovers where to resume via ``latest()`` /
``restore_or_init()``.

Directory layout (``repro.checkpoint`` package docstring has the full spec)::

    <dir>/manifest.json                  the commit point (tmp + os.replace)
    <dir>/<name>_<step:08d>.npz          flat arrays, atomic
    <dir>/<name>_<step:08d>.treedef.txt  str(treedef) sidecar, atomic

Because the manifest is written strictly AFTER its checkpoint files, a crash
anywhere mid-save leaves the manifest pointing at the previous fully-published
step — a torn pair can exist on disk but can never be *referenced*.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import platform
import time
from typing import Any

import jax
import numpy as np

from repro.checkpoint.checkpointer import restore_checkpoint, save_checkpoint

__all__ = ["CheckpointManager", "config_fingerprint"]

_MANIFEST_FORMAT = 1


def config_fingerprint(config: Any) -> str:
    """Stable short fingerprint of a run configuration.

    The canonical input is ``repro.api.ExperimentSpec`` (or its
    ``to_dict()``): the spec is the one serializable description of a run,
    so its fingerprint is the manifest's compatibility guard — ANY spec
    field change yields a different fingerprint.  Also accepts anything
    JSON-serializable-ish (objects with ``to_dict()`` are converted through
    it, dataclasses via ``dataclasses.asdict``; unknown leaves fall back to
    ``repr``).  Two processes agreeing on the fingerprint is the manager's
    guard against resuming a run under a silently different configuration."""
    if hasattr(config, "to_dict"):
        config = config.to_dict()
    elif dataclasses.is_dataclass(config) and not isinstance(config, type):
        config = dataclasses.asdict(config)
    blob = json.dumps(config, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _treedef_hash(state) -> str:
    treedef = jax.tree_util.tree_structure(state)
    return hashlib.sha256(str(treedef).encode()).hexdigest()[:16]


class CheckpointManager:
    """Step-numbered atomic checkpoints + manifest + retention + discovery.

    Parameters
    ----------
    directory:
        Where checkpoints and the manifest live (created on first use).
    keep_last:
        Retain the newest ``keep_last`` steps; older checkpoint files are
        deleted when a new step is published (the manifest's ``steps`` list
        is the authoritative record of what is retained).
    fingerprint:
        Optional ``config_fingerprint(...)`` of the run configuration.  It is
        recorded in the manifest on save and validated on restore: resuming
        with a different fingerprint raises instead of silently mixing
        configurations (segment boundaries, key streams, and metric-buffer
        shapes are all config-derived).
    name:
        Basename prefix for checkpoint files.
    """

    def __init__(
        self,
        directory: str,
        *,
        keep_last: int = 3,
        fingerprint: str | None = None,
        name: str = "state",
        layout=None,
    ):
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        self.directory = str(directory)
        self.keep_last = int(keep_last)
        self.fingerprint = fingerprint
        self.name = name
        # Optional repro.launch.mesh.ShardSpec describing the saving run's
        # sampler (N,)-axis layout.  Recorded in the manifest as PROVENANCE,
        # never validated on restore: checkpoints round-trip through host
        # numpy, so a restoring process lays the arrays out per its OWN
        # ShardSpec — resuming onto a different mesh shape is legal.
        self.layout = layout
        # What this manager has published: steps saved and the bytes of
        # their checkpoint files (the .npz and its treedef sidecar).
        self.saves = 0
        self.bytes_written = 0

    # -- paths ---------------------------------------------------------------
    @property
    def manifest_path(self) -> str:
        return os.path.join(self.directory, "manifest.json")

    def checkpoint_path(self, step: int) -> str:
        return os.path.join(self.directory, f"{self.name}_{int(step):08d}.npz")

    # -- manifest ------------------------------------------------------------
    def read_manifest(self) -> dict | None:
        """The committed manifest dict, or None if nothing was ever published."""
        try:
            with open(self.manifest_path) as f:
                return json.load(f)
        except FileNotFoundError:
            return None

    def _write_manifest(self, manifest: dict) -> None:
        os.makedirs(self.directory, exist_ok=True)
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        os.replace(tmp, self.manifest_path)  # the atomic commit point

    # -- save / discover / restore -------------------------------------------
    def save(self, state, step: int) -> str:
        """Publish ``state`` as step ``step``: files first, then the manifest.

        Returns the checkpoint ``.npz`` path.  Applies retention after the
        manifest commit (deleting a stale file can never un-commit a step)."""
        step = int(step)
        fname = save_checkpoint(self.checkpoint_path(step), state)
        self.saves += 1
        self.bytes_written += os.path.getsize(fname) + os.path.getsize(
            fname[: -len(".npz")] + ".treedef.txt"
        )
        prev = self.read_manifest()
        steps = sorted(set((prev.get("steps", []) if prev else [])) | {step})
        retained = steps[-self.keep_last :]
        manifest = {
            "format": _MANIFEST_FORMAT,
            "name": self.name,
            "step": max(retained),
            "file": os.path.basename(fname),
            "steps": retained,
            "treedef_sha256": _treedef_hash(state),
            "config_fingerprint": self.fingerprint,
            "shard_layout": (
                self.layout.to_manifest() if self.layout is not None else None
            ),
            "versions": {
                "jax": jax.__version__,
                "numpy": np.__version__,
                "python": platform.python_version(),
            },
        }
        self._write_manifest(manifest)
        for stale in steps[: -self.keep_last]:
            for path in (
                self.checkpoint_path(stale),
                self.checkpoint_path(stale)[: -len(".npz")] + ".treedef.txt",
            ):
                try:
                    os.remove(path)
                except FileNotFoundError:
                    pass
        return fname

    def latest(self) -> int | None:
        """Newest committed step whose checkpoint file exists, else None."""
        manifest = self.read_manifest()
        if manifest is None:
            return None
        for step in sorted(manifest.get("steps", [manifest["step"]]), reverse=True):
            if os.path.exists(self.checkpoint_path(step)):
                return int(step)
        return None

    def wait_for_next(
        self,
        after_step: int,
        timeout: float,
        *,
        poll_interval: float = 0.05,
    ) -> int | None:
        """Block until a step > ``after_step`` is committed; return it.

        The read side of the hand-off contract for a *concurrently writing*
        manager (a training process publishing boundaries while a serving
        process follows — ``repro.serve.CheckpointWatcher``):

        * Readers can never observe a partially written step.  ``save``
          writes the checkpoint files first and the manifest last, and the
          manifest lands via tmp-file + ``os.replace`` — POSIX-atomic, so a
          concurrent ``read_manifest`` sees either the previous complete
          manifest or the new complete one, never a torn JSON, and any step
          the manifest references already has its files fully on disk.
        * ``latest()`` additionally requires the step's ``.npz`` to exist,
          so a retention race (the writer deleting a stale step between the
          manifest read and the file check) degrades to the next-newest
          retained step, never to a dangling reference.

        Polls ``latest()`` every ``poll_interval`` seconds; returns the
        newest committed step ``> after_step`` as soon as one is visible, or
        ``None`` once ``timeout`` seconds elapse without one.  ``timeout=0``
        is a single non-blocking check."""
        after = int(after_step)
        deadline = time.monotonic() + float(timeout)
        while True:
            step = self.latest()
            if step is not None and step > after:
                return int(step)
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            time.sleep(min(float(poll_interval), remaining))

    def restore(self, template, step: int | None = None):
        """Restore step ``step`` (default: ``latest()``) into ``template``.

        Validates, in order: the manifest's config fingerprint against this
        manager's (when both are set), the manifest's treedef hash against
        the template's, then ``restore_checkpoint``'s own treedef-string /
        shape / dtype checks against the files themselves."""
        manifest = self.read_manifest()
        if manifest is None:
            raise FileNotFoundError(f"no manifest under {self.directory!r}")
        if step is None:
            step = self.latest()
            if step is None:
                raise FileNotFoundError(
                    f"manifest exists but no checkpoint files under {self.directory!r}"
                )
        saved_fp = manifest.get("config_fingerprint")
        if self.fingerprint and saved_fp and saved_fp != self.fingerprint:
            raise ValueError(
                f"config fingerprint mismatch: checkpoint was written by a run "
                f"with fingerprint {saved_fp}, this run has {self.fingerprint} "
                "— refusing to resume under a different configuration"
            )
        if int(step) == manifest["step"]:
            want = _treedef_hash(template)
            have = manifest.get("treedef_sha256")
            if have and have != want:
                raise ValueError(
                    f"treedef hash mismatch: manifest has {have}, template "
                    f"hashes to {want} — the carry structure changed"
                )
        return restore_checkpoint(self.checkpoint_path(int(step)), template)

    def restore_or_init(self, template):
        """(state, step): the latest committed state, or (template, 0) fresh.

        The standard resume entry point: build the fresh initial state as the
        template, then continue from wherever the manifest says the previous
        process got to — or from round 0 if it never published anything."""
        step = self.latest()
        if step is None:
            return template, 0
        return self.restore(template, step), int(step)
