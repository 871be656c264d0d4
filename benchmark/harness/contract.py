"""What the manifest owes its files and a configuration owes its source, as
functions: ``tests/perfbench`` holds the tree to them, and a rehearsal holds
an addition to them before it is made. They hold for a manifest of any
length and in any order."""

import os
import re


def reporting(man: dict, metric: dict) -> set:
    """The cells that report an end-to-end metric: those it lists, else all."""
    return set(metric.get("workloads", [c["name"] for c in man["workloads"]]))


def readers_of(man: dict, cell: str) -> list:
    """The per-layer entries a cell's traced run has to report: those that
    list it, and those that list no cell and move a metric it reports."""
    e2e = {m["name"]: m for m in man["end_to_end"]}
    return [m["name"] for m in man["per_layer"]
            if cell in m.get("workloads", reporting(man, e2e[m["moves"]]))]


def reader_problems(man: dict, root: str) -> list:
    """Every ``per_layer`` entry has its ``benchmark/metrics/<name>.py`` with
    a ``read(ctx)``, and every file there is listed: an orphan is a reader
    that no run reads. Returns what is wrong, in words."""
    folder = os.path.join(root, "benchmark", "metrics")
    listed = [m["name"] for m in man["per_layer"]]
    found = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    wrong = [f"{n}: listed twice" for n in set(listed) if listed.count(n) > 1]
    wrong += [f"{n}: no benchmark/metrics/{n}.py" for n in listed if n not in found]
    wrong += [f"benchmark/metrics/{n}.py: not in per_layer"
              for n in sorted(found - set(listed))]
    for n in sorted(found & set(listed)):
        with open(os.path.join(folder, n + ".py")) as f:
            if "def read(ctx)" not in f.read():
                wrong.append(f"benchmark/metrics/{n}.py: no read(ctx)")
    return wrong


# how many of something a chip holds may be its share of a deployment
_COUNTED = {"layer", "layers", "block", "blocks", "expert", "experts", "head",
            "heads", "groups", "speakers", "bins"}
_WIDTH_WORDS = {"hidden", "intermediate", "inner", "latent", "state", "proj",
                "projection", "expand", "expansion", "filter", "embd", "embed",
                "embedding", "channels", "model", "ff", "ffn", "mlp"}
_WIDTH_ENDS = {"dim", "rank", "size", "width", "factor", "hidden"}


def names_a_width(key: str) -> bool:
    """Whether a key of ``reduced`` names a width, which may never be cut: a
    hidden, intermediate, latent, state or projection size, a key that ends
    in ``_dim`` or ``_rank``, a head size, an expansion factor, the number of
    experts per token. By what the key means, word by word: a count of
    layers, of experts or heads held, or of vocabulary rows is no width
    (``num_hidden_layers`` counts layers), whatever words it shares."""
    words = [w for w in re.split(r"[_.\-]+", key.lower()) if w]
    if not words:
        return True
    if ("per" in words and {"tok", "token"} & set(words)) \
            or words[-2:] == ["top", "k"] or "selected" in words:
        return True  # experts per token
    if words[0] in ("vocab", "vocabulary"):
        return False
    if words[-1] in _COUNTED:
        return False
    return words[-1] in _WIDTH_ENDS or bool(_WIDTH_WORDS & set(words)) \
        or words[0] == "d"
