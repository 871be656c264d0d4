"""Orbax checkpointing: step-named save/restore with partial loading.

Replaces the reference's ``torch.save({"model", "optimizer"})`` every
save_step (reference: train.py:155-165) and its ``ignore_layers`` +
``strict=False`` transfer-learning restore (reference: utils/model.py:15-32,
config/BC2013/train.yaml:1).

Resilience extensions (ISSUE 2, config: ``train.resilience.*``):

  * **async saves** — ``save()`` snapshots the state to host memory
    synchronously (donation safety: the next step may reuse the device
    buffers) and hands the Orbax write to a background thread, so the
    step loop never blocks on checkpoint I/O. ``wait()`` joins the
    in-flight write and re-raises any write error.
  * **retention** — keep the newest ``max_to_keep`` steps, plus (with
    ``keep_best``) the best-val-loss step, pruned after each save.
  * **robust latest-step restore** — ``restore(step=None)`` walks steps
    newest-first and falls back past a partial/corrupt checkpoint
    directory (crashed mid-write) instead of bricking the resume,
    distinguishing corrupt (``ckpt_corrupt_skipped`` event + counter)
    from merely absent.

Integrity (ISSUE 13): every save writes ``<step>/manifest.json`` — the
per-leaf sha256 table, tree structure, step, an optional config
fingerprint, and the params-wide ``weights_digest`` — via a temp file +
``os.replace`` so the manifest is atomic: it exists iff it is complete.
Restore verifies the manifest BEFORE handing anything to the caller and
raises ``CheckpointCorruptError`` (structured: ``.step``/``.reason``),
which is a different failure than "no checkpoint here". Manifests are
only advisory for pre-manifest checkpoints (``strict=False`` tolerates
their absence); a rollout's verify gate restores with ``strict=True``.
The ``checkpoint_corrupt@N`` / ``manifest_missing@N`` fault kinds
(faults.py) drill both paths deterministically, counted per manager
instance on the 1-based verification counter ``verify_count``.

Sharding awareness / cross-mesh-shape resume (ISSUE 10): the on-disk
format is mesh-agnostic — ``save()``'s device->host snapshot
(``jax.device_get``) assembles full global arrays whatever DP/TP layout
the live state carried — and ``restore()`` builds its abstract template
from the *passed* state, preserving any shardings its leaves carry. Pass
a state already laid out for the TARGET mesh (or
``TrainState.sharded_abstract``) and Orbax materializes each leaf
directly into that layout: save on an 8x1 DP mesh, restore onto 4x2
DP×TP or 1x1 single-chip, bit-identically (tests/test_multichip.py).
"""

import json
import os
import re
import threading
from typing import Dict, List, Optional, Sequence

import jax
import orbax.checkpoint as ocp

from speakingstyle_tpu.obs.buildinfo import leaf_sha256, weights_digest
from speakingstyle_tpu.training.state import TrainState
from speakingstyle_tpu.obs.locks import make_lock

MANIFEST_NAME = "manifest.json"
MANIFEST_FORMAT = 1


class CheckpointCorruptError(RuntimeError):
    """A checkpoint EXISTS but failed integrity verification — distinct
    from FileNotFoundError (absent). Carries the step and a machine-
    readable reason (``manifest_missing``, ``manifest_malformed``,
    ``leaf_set_mismatch``, ``leaf_hash_mismatch``, ``injected``)."""

    def __init__(self, step: int, reason: str, detail: str = ""):
        self.step = step
        self.reason = reason
        msg = f"checkpoint step {step} is corrupt ({reason})"
        if detail:
            msg += f": {detail}"
        super().__init__(msg)


def _abstract_leaf(x):
    """Shape/dtype(/sharding) template leaf for StandardRestore."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    return ocp.utils.to_shape_dtype_struct(x)


def _leaf_table(tree) -> Dict[str, Dict]:
    """{'/'-joined leaf path: {sha256, shape, dtype}} for a host tree.
    The same naming as the manifest verifier and ``weights_digest`` use,
    so one flattening convention covers save, verify, and identity."""
    import numpy as np

    shas = leaf_sha256(tree)
    leaves = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]
    return {
        name: {"sha256": sha, "shape": list(a.shape), "dtype": str(a.dtype)}
        for (name, sha), a in zip(shas.items(), leaves)
    }


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        max_to_keep: Optional[int] = None,
        async_save: bool = False,
        keep_best: bool = False,
        fault_plan=None,
        events=None,
        registry=None,
        config_fingerprint: Optional[str] = None,
        verify: bool = True,
    ):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        # retention is implemented here (max_to_keep + keep-best protection),
        # not by Orbax options — Orbax's max_to_keep cannot pin the best
        # step past the window
        self.manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=None, create=True, enable_async_checkpointing=False
            ),
        )
        self.max_to_keep = max_to_keep or None
        self.keep_best = keep_best
        self.async_save = async_save
        self._metrics: Dict[int, float] = {}  # step -> val loss
        self._lock = make_lock("CheckpointManager._lock")
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.fault_plan = fault_plan
        self.events = events
        self.registry = registry
        self.config_fingerprint = config_fingerprint
        self.verify = verify
        self.verify_count = 0  # 1-based fault-site counter (per instance)
        self.last_restored_step: Optional[int] = None
        self.last_weights_digest: Optional[str] = None

    # -- saving -------------------------------------------------------------

    def save(
        self,
        step: int,
        state,
        val_loss: Optional[float] = None,
        block: bool = False,
    ):
        """Save ``state`` under ``step``. With ``async_save`` the Orbax
        write runs on a background thread and this returns as soon as the
        device->host snapshot is taken; pass ``block=True`` (final/flush
        saves) to wait for the write. ``val_loss`` feeds keep-best
        retention."""
        self.wait()  # one write in flight at a time; surfaces prior errors
        host_state = jax.device_get(state)
        if self.async_save and not block:
            self._thread = threading.Thread(
                target=self._write_guarded,
                args=(step, host_state, val_loss),
                name=f"ckpt-save-{step}",
                daemon=True,
            )
            self._thread.start()
        else:
            self._write(step, host_state, val_loss)

    def _write_guarded(self, step: int, host_state, val_loss):
        try:
            self._write(step, host_state, val_loss)
        except BaseException as e:  # surfaced by the next wait()/save()
            self._error = e

    def _write(self, step: int, host_state, val_loss):
        self.manager.save(step, args=ocp.args.StandardSave(host_state))
        self.manager.wait_until_finished()
        self._write_manifest(step, host_state)
        with self._lock:
            if val_loss is not None:
                self._metrics[step] = float(val_loss)
        self._prune()

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.directory, str(step), MANIFEST_NAME)

    def _write_manifest(self, step: int, host_state):
        """The integrity record, atomic via temp + os.replace: a torn
        write leaves no manifest at all (absent, never malformed)."""
        manifest = {
            "format": MANIFEST_FORMAT,
            "step": int(step),
            "config_fingerprint": self.config_fingerprint,
            "weights_digest": weights_digest(
                getattr(host_state, "params", host_state)
            ),
            "leaves": _leaf_table(host_state),
        }
        path = self._manifest_path(step)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=1, sort_keys=True)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)

    def save_in_flight(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def wait(self):
        """Join any in-flight async write; re-raise its error, if any."""
        t = self._thread
        if t is not None:
            t.join()
            self._thread = None
        err, self._error = self._error, None
        if err is not None:
            raise err

    # -- retention ----------------------------------------------------------

    def best_step(self) -> Optional[int]:
        """Step with the lowest recorded val loss (this process only)."""
        with self._lock:
            if not self._metrics:
                return None
            return min(self._metrics, key=self._metrics.get)

    def _prune(self):
        if not self.max_to_keep:
            return
        steps = sorted(self.manager.all_steps())
        keep = set(steps[-self.max_to_keep:])
        best = self.best_step()
        if self.keep_best and best is not None:
            keep.add(best)
        for s in steps:
            if s not in keep:
                try:
                    self.manager.delete(s)
                except FileNotFoundError:
                    pass  # already gone (e.g. a concurrent manual cleanup)

    # -- reading ------------------------------------------------------------

    def all_steps(self) -> List[int]:
        return sorted(self.manager.all_steps())

    def _load_manifest(self, step: int) -> Optional[Dict]:
        """Parse the step's manifest, or None when absent. Malformed
        JSON is CORRUPT, not absent: the atomic writer never leaves a
        half manifest, so a torn file means the directory was damaged."""
        path = self._manifest_path(step)
        if not os.path.isfile(path):
            return None
        try:
            with open(path, encoding="utf-8") as fh:
                manifest = json.load(fh)
        except (OSError, ValueError) as e:
            raise CheckpointCorruptError(
                step, "manifest_malformed", f"{type(e).__name__}: {e}"
            ) from e
        if not isinstance(manifest, dict) or "leaves" not in manifest:
            raise CheckpointCorruptError(
                step, "manifest_malformed", "no leaf table"
            )
        return manifest

    def _verify_restored(self, step: int, manifest: Dict, restored):
        """Per-leaf hash comparison of the materialized tree against the
        manifest written at save time."""
        got = _leaf_table(jax.device_get(restored))
        want = manifest["leaves"]
        if set(got) != set(want):
            missing = sorted(set(want) - set(got))[:3]
            extra = sorted(set(got) - set(want))[:3]
            raise CheckpointCorruptError(
                step, "leaf_set_mismatch",
                f"missing={missing} extra={extra}",
            )
        bad = [n for n in want if got[n]["sha256"] != want[n]["sha256"]]
        if bad:
            raise CheckpointCorruptError(
                step, "leaf_hash_mismatch",
                f"{len(bad)} leaves, first: {sorted(bad)[:3]}",
            )

    def _restore_step(self, step: int, abstract, strict: bool = False):
        """Restore one step via a standalone checkpointer aimed straight
        at the step's item directory. The CheckpointManager is NOT used
        here on purpose: a single failed ``manager.restore`` (a corrupt
        step directory) permanently flips its item-handler registry into
        multi-item mode, after which every later restore — including of
        healthy steps — fails. The standalone path is stateless, so the
        newest-first fallback scan can keep probing.

        The manifest is checked BEFORE materializing (a malformed one
        never costs a restore) and the per-leaf hashes after; either
        failure raises CheckpointCorruptError. ``strict`` additionally
        treats a missing manifest as corrupt (rollout verify gates);
        the default tolerates pre-manifest checkpoints."""
        path = os.path.join(self.directory, str(step), "default")
        if not os.path.isdir(path):
            raise FileNotFoundError(f"no checkpoint item at {path}")
        self.verify_count += 1
        n = self.verify_count
        plan = self.fault_plan
        if plan is not None and plan.fire("checkpoint_corrupt", n):
            raise CheckpointCorruptError(step, "injected", "fault drill")
        manifest = None
        if self.verify:
            if plan is not None and plan.fire("manifest_missing", n):
                manifest = None  # drill: behave as if never written
            else:
                manifest = self._load_manifest(step)
            if manifest is None and strict:
                raise CheckpointCorruptError(
                    step, "manifest_missing",
                    "strict restore requires a save-time manifest",
                )
        restored = ocp.StandardCheckpointer().restore(path, abstract)
        if manifest is not None:
            self._verify_restored(step, manifest, restored)
            self.last_weights_digest = manifest.get("weights_digest")
        else:
            # legacy checkpoint: identity computed, not verified
            self.last_weights_digest = weights_digest(
                getattr(restored, "params", restored)
            )
        self.last_restored_step = step
        return restored

    def latest_step(self) -> Optional[int]:
        return self.manager.latest_step()

    def restore(
        self,
        state,
        step: Optional[int] = None,
        ignore_layers: Sequence[str] = (),
        strict: bool = False,
    ) -> TrainState:
        """Restore into the shape — and SHARDINGS — of ``state`` (concrete
        arrays or a jax.ShapeDtypeStruct template, e.g.
        ``TrainState.abstract()`` / ``TrainState.sharded_abstract()``).
        Cross-mesh resume rides this: the template names the target mesh's
        layout and Orbax materializes straight into it.

        ``step=None`` restores the latest step, falling back past
        partial/corrupt checkpoint directories (newest-first) so one
        crashed write cannot brick a resume — each corrupt (not merely
        absent) step skipped emits a ``ckpt_corrupt_skipped`` event and
        bumps ``ckpt_corrupt_skipped_total``. An explicitly requested
        step fails loudly instead. ``strict=True`` (rollout verify)
        refuses manifest-less checkpoints.

        ignore_layers: regexes matched against '/'-joined param paths;
        matching leaves keep their freshly-initialized values AND the
        optimizer state is reset (the reference reinitializes the
        optimizer when transferring). Requires concrete ``state``.
        """
        self.wait()  # never read around an in-flight write
        abstract = jax.tree_util.tree_map(_abstract_leaf, state)
        candidates = (
            [step] if step is not None else sorted(self.all_steps(), reverse=True)
        )
        if not candidates:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        restored = None
        failures = []
        for s in candidates:
            try:
                restored = self._restore_step(s, abstract, strict=strict)
                break
            except Exception as e:
                if step is not None:
                    raise
                failures.append((s, f"{type(e).__name__}: {e}"))
                # corrupt-vs-absent triage: an absent item directory is a
                # routine hole in the walk; anything else means the step
                # EXISTS and is damaged — observable, never silent
                if not isinstance(e, FileNotFoundError):
                    self._note_corrupt_skip(s, e)
                print(
                    f"[checkpoint] step {s} under {self.directory} is not "
                    f"restorable ({type(e).__name__}); trying the previous step"
                )
        if restored is None:
            raise FileNotFoundError(
                f"no restorable checkpoint under {self.directory}: "
                f"all candidates failed: {failures}"
            )
        if ignore_layers:
            patterns = [re.compile(p) for p in ignore_layers]

            def merge(path, fresh, loaded):
                name = "/".join(str(getattr(k, "key", k)) for k in path)
                return fresh if any(p.search(name) for p in patterns) else loaded

            params = jax.tree_util.tree_map_with_path(
                merge, state.params, restored.params
            )
            return state.replace(params=params, batch_stats=restored.batch_stats)
        return restored

    def _note_corrupt_skip(self, step: int, error: BaseException) -> None:
        reason = getattr(error, "reason", type(error).__name__)
        if self.registry is not None:
            self.registry.counter(
                "ckpt_corrupt_skipped_total",
                help="corrupt (not absent) checkpoints skipped by the "
                     "newest-first restore walk",
            ).inc()
        if self.events is not None:
            self.events.emit(
                "ckpt_corrupt_skipped", step=int(step), reason=str(reason),
                error=f"{type(error).__name__}: {error}",
            )

    def close(self):
        try:
            self.wait()
        except BaseException as e:
            # close() runs in ``finally`` blocks: surface, don't mask
            print(f"[checkpoint] in-flight save failed during close: {e}")
        self.manager.close()
