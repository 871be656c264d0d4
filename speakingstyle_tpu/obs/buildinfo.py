"""Build/runtime identity + process gauges: *what* is this process?

Every scrape and every training run should identify the code and stack
that produced it — a benchmark line or a /metrics snapshot without a git
SHA and a jax version is unattributable a week later. ``build_info()``
collects the identity once (git SHA when the tree is a checkout, jax /
jaxlib versions, backend platform + device count/kind, python); the
serving ``/healthz`` payload and the trainer's ``train_start`` event
both carry it.

``process_rss_bytes()`` reads the resident set from ``/proc/self/status``
(falling back to ``resource.getrusage`` peak-RSS elsewhere) so
``GET /metrics`` can export ``process_rss_bytes`` + ``process_uptime_seconds``
— the two gauges that turn a scrape into "which process, how long up,
how big".

``weights_digest()`` extends the identity from *code* to *model*: a
single sha256 over a pytree of weights (order-independent: sorted
per-leaf hashes), so ``/healthz``, ``train_start`` and the serving
``X-Model-Version`` header can pin WHICH weights a process is running —
the complement of the per-leaf manifest ``training/checkpoint.py``
verifies at restore time.

Everything degrades to ``None``/absent rather than raising: no git, no
jax, no /proc must not take down a health endpoint.
"""

import hashlib
import os
import platform
import subprocess
from typing import Dict, Optional


def git_sha(cwd: Optional[str] = None) -> Optional[str]:
    """HEAD commit of the tree containing this package, or None."""
    cwd = cwd or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=5, cwd=cwd,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def build_info() -> Dict:
    """Identity dict for /healthz and the train_start event. jax is
    imported lazily and optional — the function works on a login node."""
    info: Dict = {
        "git_sha": git_sha(),
        "python": platform.python_version(),
    }
    try:
        import jax
        import jaxlib

        info["jax"] = jax.__version__
        info["jaxlib"] = getattr(jaxlib, "__version__", None)
        devs = jax.devices()
        info["backend"] = devs[0].platform if devs else jax.default_backend()
        info["device_count"] = len(devs)
        info["device_kind"] = getattr(devs[0], "device_kind", "") if devs else ""
    except Exception as e:
        info["jax_error"] = f"{type(e).__name__}: {e}"
    return info


def array_sha256(arr) -> str:
    """sha256 of one array's dtype + shape + raw bytes (host-side; the
    caller device_gets first). Dtype and shape are hashed so a reshape
    or cast never collides with the original. The bytes are read in
    place through ``update``, which lets go of the GIL, so that
    ``leaf_sha256`` can hash leaves side by side."""
    import numpy as np

    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.reshape(-1).view(np.uint8))
    return h.hexdigest()


def leaf_sha256(tree) -> Dict[str, str]:
    """{'/'-joined leaf path: ``array_sha256``} of a host pytree, the
    leaves hashed on a few threads: gigabytes of state are a checkpoint's
    manifest and a restore's verification, and one thread hashes about a
    gigabyte a second."""
    import jax
    from concurrent.futures import ThreadPoolExecutor

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for path, _ in leaves
    ]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return dict(zip(names, pool.map(array_sha256, [l for _, l in leaves])))


def weights_digest(tree) -> Optional[str]:
    """One order-independent sha256 over a whole weight pytree, or None
    when it cannot be computed (no jax, abstract leaves, empty tree).
    Feeding sorted ``name=leaf_sha`` lines into a single hash makes the
    digest stable across flattening order and mesh layout — the same
    weights give the same digest on 8x1 DP and 1x1 single-chip."""
    try:
        table = leaf_sha256(tree)
        if not table:
            return None
        h = hashlib.sha256()
        for line in sorted(f"{name}={sha}\n" for name, sha in table.items()):
            h.update(line.encode())
        return h.hexdigest()
    except Exception as e:
        # identity must degrade, never raise (abstract leaves, no jax on
        # a login node): absent-with-a-trace beats a dead health endpoint
        print(f"[buildinfo] weights_digest unavailable: "
              f"{type(e).__name__}: {e}")
        return None


def process_rss_bytes() -> Optional[float]:
    """Current resident set size in bytes (Linux /proc; peak-RSS via
    getrusage elsewhere), or None when neither source works."""
    try:
        with open("/proc/self/status", encoding="ascii") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) * 1024.0
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return float(peak_kb) * 1024.0
    except (ImportError, OSError, ValueError):  # windows / exotic libc
        return None
