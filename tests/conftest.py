"""Test harness: force an 8-device virtual CPU mesh.

Must run before any JAX backend initialization. The JAX analogue of a fake
multi-device backend (the reference has no such thing — SURVEY.md §4): all
sharding/collective tests run on 8 virtual CPU devices.
"""

import atexit
import os
import shutil
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Hermetic compile cache: the program keeps its persistent cache where this
# variable points (obs/jaxmon.enable_compilation_cache), so a per-session
# directory means tests — and the child processes they spawn — neither read
# nor fill the checkout's .jax_cache. Set before jax is imported: jax reads
# the variable once, at import.
_SESSION_CACHE = tempfile.mkdtemp(prefix="jax_cache_tests_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _SESSION_CACHE
atexit.register(shutil.rmtree, _SESSION_CACHE, ignore_errors=True)

import jax

# Unit tests are CPU-only by design, whatever accelerator the host has.
jax.config.update("jax_platforms", "cpu")

import json

import numpy as np
import pytest


@pytest.fixture
def synthetic_preprocessed(tmp_path):
    """A tiny on-disk preprocessed dataset in the reference layout
    (mel/pitch/energy/duration .npy + metadata + speakers/stats json)."""
    root = tmp_path / "preprocessed"
    for kind in ("mel", "pitch", "energy", "duration"):
        (root / kind).mkdir(parents=True)
    rng = np.random.default_rng(0)
    lines = []
    n_items = 13
    for i in range(n_items):
        basename, speaker = f"utt{i:03d}", "LJSpeech"
        n_ph = int(rng.integers(5, 40))
        durations = rng.integers(1, 8, size=n_ph)
        n_frames = int(durations.sum())
        np.save(root / "mel" / f"{speaker}-mel-{basename}.npy",
                rng.standard_normal((n_frames, 80)).astype(np.float32))
        np.save(root / "pitch" / f"{speaker}-pitch-{basename}.npy",
                rng.standard_normal(n_ph).astype(np.float32))
        np.save(root / "energy" / f"{speaker}-energy-{basename}.npy",
                rng.standard_normal(n_ph).astype(np.float32))
        np.save(root / "duration" / f"{speaker}-duration-{basename}.npy",
                durations.astype(np.int64))
        phones = " ".join(rng.choice(["AH0", "K", "T", "EH1", "sp"], n_ph))
        lines.append(f"{basename}|{speaker}|{{{phones}}}|dummy text {i}")
    (root / "train.txt").write_text("\n".join(lines[:10]) + "\n")
    (root / "val.txt").write_text("\n".join(lines[10:]) + "\n")
    (root / "speakers.json").write_text(json.dumps({"LJSpeech": 0}))
    (root / "stats.json").write_text(json.dumps({
        "pitch": [-2.5, 9.0, 0.0, 1.0], "energy": [-1.5, 8.0, 0.0, 1.0],
    }))
    return str(root)
