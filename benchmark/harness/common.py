"""What every cell's driver shares: the manifest, the cell's files, a working
directory inside the checkout, the program's YAML files written from the
configuration's JSON, the device report and the result line."""

import atexit
import json
import os
import shutil
import signal
import sys
import tempfile
import time

T0 = time.time()  # process start, as near as Python lets us see it

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def load_json(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def manifest():
    """``BENCHMARK.json``: the only manifest."""
    return load_json("BENCHMARK.json")


def cell_files(workload: str):
    """(cell, config entry, configuration JSON, traffic JSON) for one cell."""
    man = manifest()
    cells = {c["name"]: c for c in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in man["configs"]}[cell["config"]]
    traffic = load_json(f"benchmark/traffic/{cell['traffic']}.json")
    return cell, entry, load_json(entry["file"]), traffic


def load_module(rel: str, name: str):
    """A file of the benchmark as a module of its own: a configuration's
    bindings, a metric's reader. Found by path, since names carry dots."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sized(block: dict, toy: bool) -> dict:
    """A configuration or traffic file with its ``toy`` block laid over it
    (CPU rehearsals and tests only) and the block itself removed."""
    out = {k: v for k, v in block.items() if k != "toy"}
    if toy:
        for k, v in block.get("toy", {}).items():
            merge = (isinstance(v, dict) and isinstance(out.get(k), dict)
                     and not k.endswith("_quantiles"))  # a table is replaced
            out[k] = {**out[k], **v} if merge else v
    return out


def workdir() -> str:
    """A fresh directory of this process's own under ``<checkout>/.bench_work``
    (``mkdtemp``: never a path that was there), removed at exit and on
    SIGTERM. One location and no fallback: where it cannot be made the run
    fails. Holds the corpus, logs, checkpoints and traces of one run."""
    base = os.path.join(ROOT, ".bench_work")
    os.makedirs(base, exist_ok=True)
    path = tempfile.mkdtemp(prefix="run-", dir=base)
    atexit.register(shutil.rmtree, path, ignore_errors=True)
    # a run killed at its time limit gets SIGTERM first: leave through
    # SystemExit so that the directory goes (the trainer holds the signal
    # itself while its loop runs, and gives this handler back after)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    log(f"workdir: {path} ({fs_type(path)})")
    return path


def fs_type(path: str) -> str:
    """The file system ``path`` is on, as ``/proc/mounts`` names it."""
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mount, fs = line.split()[:3]
                if os.path.realpath(path).startswith(mount) and len(mount) > len(best):
                    best, kind = mount, fs
    except OSError:
        pass
    return kind


def write_program_configs(cfg: dict, work: str, corpus: str, lexicon: str,
                          step: dict = None) -> dict:
    """The program's three YAML files: the preset the configuration names,
    with paths pointed into ``work``, the model block as the configuration's
    JSON states it (the keys of ``model`` that its ``program_model_keys``
    lists are the program's; the others are the reference's alone), and the
    step block the cell runs with."""
    import yaml

    preset = os.path.join(ROOT, "speakingstyle_tpu", "configs", "presets",
                          cfg["preset"])

    def load(name):
        with open(os.path.join(preset, name)) as f:
            return yaml.safe_load(f)

    pre, trn = load("preprocess.yaml"), load("train.yaml")
    pre["path"]["preprocessed_path"] = corpus
    pre["path"]["lexicon_path"] = lexicon
    model = {k: cfg["model"][k] for k in cfg["program_model_keys"]}
    trn["path"] = {k: os.path.join(work, k.split("_")[0])
                   for k in ("ckpt_path", "log_path", "result_path")}
    trn["seed"] = int(cfg.get("program_seed", 1234))
    trn["optimizer"].update(cfg.get("optimizer_overrides", {}))
    if step:
        trn["step"] = step
    os.makedirs(os.path.join(work, "cfg"), exist_ok=True)
    paths = {}
    for name, body in (("preprocess", pre), ("model", model), ("train", trn)):
        paths[name] = os.path.join(work, "cfg", f"{name}.yaml")
        with open(paths[name], "w") as f:
            yaml.safe_dump(body, f)
    return {"paths": paths, "train": trn, "preprocess": pre}


def optimizer_for_reference(trn: dict) -> dict:
    o = trn["optimizer"]
    return {"betas": tuple(o["betas"]), "eps": o["eps"],
            "clip": o["grad_clip_thresh"], "init_lr": o["init_lr"],
            "anneal_lr": o["anneal_lr"], "anneal_rate": o["anneal_rate"],
            "anneal_steps": list(o["anneal_steps"]),
            "ramp_steps": trn["loss"]["anneal_steps"]}


def require_chip(chips: int, toy: bool) -> dict:
    """The device as JAX reports it. Exits non-zero, printing no result, when
    there is no accelerator or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    report = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not toy and (report["platform"] == "cpu" or report["count"] < chips):
        log(f"device guard: JAX reports {report}; the cell needs {chips} "
            "accelerator chip(s). A CPU run measures nothing.")
        raise SystemExit(3)
    return report


def emit_result(correct, attempted, failed, metrics, device, compared,
                breakdown=None):
    """Compared numbers beside their limits as the last lines of stderr, then
    the one JSON line on stdout; the compared block comes last in it."""
    for name, pair in compared.items():
        log(f"compared {name}: {pair['value']} limit {pair['limit']}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def judge(readings: dict, limits: dict) -> tuple:
    """(correct, compared) from readings and the limits file's entries. A
    reading that is missing or not finite fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        compared[name] = {"value": value if good or (
            value is not None and value == value) else None, "limit": limit}
    return ok, compared


def peak_bytes(stats: dict) -> int:
    """The chip's peak from ``memory_stats()``. On this runtime the programs'
    scratch is a region reserved apart from the buffers in use: what is free
    (``largest_free_block_bytes``) is ``bytes_limit`` less both, so the peak
    is the sum of the two peaks (an upper bound where they do not coincide)."""
    stats = stats or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))
