"""Fault-tolerant checkpointing: atomic, async, keep-N, auto-resume.

Design for the 1000+-node posture:

* **atomic**: write to ``<dir>/tmp.<step>`` then ``os.replace`` into
  place — a preempted writer never corrupts the latest checkpoint;
* **async**: the host-side serialization runs on a background thread;
  the train loop only blocks if a previous save is still in flight
  (one outstanding save, bounded memory);
* **keep-N**: old steps garbage-collected after a successful save;
* **auto-resume**: ``latest_step`` scans the directory so a restarted
  job continues from the last complete checkpoint — combined with the
  seekable data stream and counter-based RNG, restart is bit-exact;
* **multi-host**: each process saves only the shards it owns
  (``process_index`` suffix); on this single-process container that is
  one file.  Restore reassembles and re-shards via
  ``jax.device_put`` with the target sharding.

Format: one ``npz`` per (step, process) holding flattened leaves +
a JSON treedef sidecar.  No external deps (orbax is not available
offline), but the same layout discipline.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, List, Optional

import jax
import numpy as np

from ..obs import resolve_recorder


def _flatten(tree: Any):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def save_pytree(tree: Any, path: str) -> None:
    """Synchronous atomic save of one pytree to ``path`` (a directory)."""
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    leaves, treedef = _flatten(tree)
    arrs = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    np.savez(os.path.join(tmp, f"shard_{jax.process_index()}.npz"),
             **arrs)
    with open(os.path.join(tmp, "treedef.json"), "w") as f:
        json.dump({"treedef": str(treedef), "n_leaves": len(leaves)}, f)
    if os.path.exists(path):
        shutil.rmtree(path)
    os.replace(tmp, path)


def load_pytree(template: Any, path: str) -> Any:
    """Load into the structure of ``template`` (shapes must match)."""
    leaves, treedef = _flatten(template)
    with np.load(os.path.join(
            path, f"shard_{jax.process_index()}.npz")) as z:
        new = [z[f"leaf_{i}"] for i in range(len(leaves))]
    for t, n in zip(leaves, new):
        if hasattr(t, "shape") and tuple(t.shape) != tuple(n.shape):
            raise ValueError(f"shape mismatch {t.shape} vs {n.shape}")
    return jax.tree.unflatten(treedef, new)


_STEP_RE = re.compile(r"^step_(\d+)$")


def list_steps(directory: str) -> List[int]:
    """Sorted steps with a COMPLETE checkpoint under ``directory``.

    Completeness = the treedef sidecar exists (it is written last,
    before the atomic rename); a preempted writer's half-saved step
    never shows up.  Used by both ``CheckpointManager`` and
    ``core.predict.PredictSession`` (which replays every saved
    posterior sample rather than just the latest state).
    """
    if not os.path.isdir(directory):
        return []
    return sorted(int(m.group(1)) for d in os.listdir(directory)
                  if (m := _STEP_RE.match(d))
                  and os.path.exists(os.path.join(directory, d,
                                                  "treedef.json")))


def latest_step(directory: str) -> Optional[int]:
    steps = list_steps(directory)
    return max(steps) if steps else None


class CheckpointManager:
    """Async keep-N checkpoint manager.

    ``keep=None`` disables garbage collection entirely — every saved
    step stays on disk.  That is the posterior-sample store mode: a
    session streaming samples via ``save_freq`` must retain ALL of
    them for ``PredictSession`` to average, unlike the rolling-restart
    checkpoints which only need the last few.
    """

    def __init__(self, directory: str, keep: Optional[int] = 3,
                 recorder: Any = None):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        # obs: save/restore durations, queue depth, bytes written.
        # The session passes its own Recorder down so checkpoint spans
        # land in the run's trace; standalone managers resolve a fresh
        # one (enabled iff REPRO_OBS=1).
        self.obs = resolve_recorder(recorder)

    def _raise_pending(self) -> None:
        """Re-raise an exception captured on the saver thread.

        A disk-full / permission error during a background save must
        not be silently lost (the sample store would be incomplete and
        nobody would know) — it surfaces from the NEXT ``save()`` or
        ``wait()`` on the training thread.  The pending error is
        cleared on raise so a handled failure doesn't re-raise forever.
        """
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                f"background checkpoint save into {self.dir!r} failed: "
                f"{err!r}") from err

    def _gc(self) -> None:
        if self.keep is None:
            return
        steps = sorted(
            int(m.group(1)) for d in os.listdir(self.dir)
            if (m := _STEP_RE.match(d)))
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"),
                          ignore_errors=True)

    def save(self, step: int, tree: Any, blocking: bool = False) -> None:
        with self.obs.span("ckpt/wait", cat="ckpt"):
            self.wait()
        # materialize on host *before* handing to the thread so the
        # device buffers can be donated/freed by the train loop
        with self.obs.span("ckpt/host_copy", cat="ckpt"):
            host = jax.tree.map(np.asarray, tree)
        nbytes = sum(int(x.nbytes) for x in jax.tree.leaves(host))

        def work():
            t0 = self.obs.now()
            with self.obs.span("ckpt/save", cat="ckpt", step=step,
                               bytes=nbytes):
                save_pytree(host, os.path.join(self.dir, f"step_{step}"))
                self._gc()
            self.obs.observe("ckpt.save_s", self.obs.now() - t0)
            self.obs.add("ckpt.saves")
            self.obs.add("ckpt.bytes_written", nbytes)

        if blocking:
            work()
        else:
            def guarded():
                try:
                    work()
                except BaseException as e:  # noqa: BLE001 — must not die silently
                    self._error = e

            # queue depth gauge: one outstanding background save max
            # (save() always wait()s first); 1 while in flight
            self.obs.gauge("ckpt.queue_depth", 1)
            self._thread = threading.Thread(target=guarded, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
            self.obs.gauge("ckpt.queue_depth", 0)
        self._raise_pending()

    def restore_latest(self, template: Any):
        """(step, tree) of the newest complete checkpoint, or None."""
        self.wait()
        step = latest_step(self.dir)
        if step is None:
            return None
        t0 = self.obs.now()
        tree = load_pytree(template,
                           os.path.join(self.dir, f"step_{step}"))
        self.obs.complete("ckpt/restore", t0, cat="ckpt", step=step)
        self.obs.observe("ckpt.restore_s", self.obs.now() - t0)
        self.obs.add("ckpt.restores")
        return step, tree

    def restore_step(self, template: Any, step: int) -> Any:
        """Load one specific saved step (multi-chain resume restores
        every chain at the HIGHEST COMMON step, not each chain's own
        latest — an interrupted run may have chains one save apart)."""
        self.wait()
        t0 = self.obs.now()
        tree = load_pytree(template,
                           os.path.join(self.dir, f"step_{step}"))
        self.obs.complete("ckpt/restore", t0, cat="ckpt", step=step)
        self.obs.observe("ckpt.restore_s", self.obs.now() - t0)
        self.obs.add("ckpt.restores")
        return tree

    def all_steps(self) -> List[int]:
        return list_steps(self.dir)
