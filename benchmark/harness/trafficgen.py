"""The one general traffic generator. A traffic mix is a JSON file of
parameters; everything here is driven by it and by the seed. The *multiset*
of sizes is a function of the file alone (a quantile table, no random draw),
so every seed does the same work; the seed orders it and fills in content.
"""

import os

import numpy as np

# The phone inventory of the synthetic corpus: 33 ARPAbet phones. Ids follow
# the published symbol layout (pad, "-", 10 punctuation marks, 52 letters,
# then "@"-prefixed ARPAbet in its standard order): id = 64 + index.
ARPABET = (
    "AA AA0 AA1 AA2 AE AE0 AE1 AE2 AH AH0 AH1 AH2 AO AO0 AO1 AO2 AW AW0 AW1 "
    "AW2 AY AY0 AY1 AY2 B CH D DH EH EH0 EH1 EH2 ER ER0 ER1 ER2 EY EY0 EY1 "
    "EY2 F G HH IH IH0 IH1 IH2 IY IY0 IY1 IY2 JH K L M N NG OW OW0 OW1 OW2 "
    "OY OY0 OY1 OY2 P R S SH T TH UH UH0 UH1 UH2 UW UW0 UW1 UW2 V W Y Z ZH"
).split()
PHONES = ("AA1 AE1 AH0 AO1 EH1 ER0 IH1 IY1 OW1 UW1 B CH D DH F G HH JH K L "
          "M N NG P R S SH T TH V W Y Z").split()
PHONE_IDS = np.asarray([64 + ARPABET.index(p) for p in PHONES], np.int32)


def quantile_deck(table: dict, n: int) -> np.ndarray:
    """n sizes from a piecewise-linear quantile table {q: size}: the value at
    (i + 0.5) / n for each i. No random draw: the deck is the file's."""
    qs = np.asarray(sorted(float(q) for q in table))
    vs = np.asarray([table[k] for k in sorted(table, key=float)], np.float64)
    return np.rint(np.interp((np.arange(n) + 0.5) / n, qs, vs)).astype(np.int64)


def permutation(n: int, seed: int, salt: int) -> np.ndarray:
    return np.random.default_rng([int(seed), salt]).permutation(n)


# -- training corpus -------------------------------------------------------

def train_deck(spec: dict) -> list:
    """[(n_phones, durations)] sorted by n_phones descending, with no tie in
    n_phones across a batch boundary, so the batcher's sort by text length
    puts the same rows in the same batch whatever the seed's order."""
    frames = np.sort(quantile_deck(spec["frames_quantiles"], spec["utterances"]))[::-1]
    n_ph = np.maximum(np.rint(frames / spec["frames_per_phone"]), 2).astype(np.int64)
    batch = spec["batch_size"]
    for b in range(batch, len(n_ph), batch):
        while n_ph[b - 1] <= n_ph[b]:
            n_ph[:b][n_ph[:b] <= n_ph[b]] += 1
    deck = []
    for f, n in zip(frames, n_ph):
        base, extra = divmod(int(f), int(n))
        # the remainder goes to evenly spaced phones
        d = np.full(n, base, np.int64)
        d[(np.arange(extra) * n) // max(extra, 1)] += 1
        deck.append((int(n), d))
    return deck


def write_corpus(out_dir: str, spec: dict, seed: int, n_mels: int = 80) -> dict:
    """The preprocessed-corpus layout the trainer reads (mel / pitch / energy
    / duration ``.npy`` per utterance, ``train.txt``, ``val.txt``,
    ``speakers.json``, ``stats.json``), learnable as the program's own
    synthetic corpus is: a fixed signature per phone, lightly noised."""
    import json

    deck = train_deck(spec)
    order = permutation(len(deck), seed, 1)
    rng = np.random.default_rng([int(seed), 2])
    sig = np.random.default_rng(1234)
    mel_sig = sig.standard_normal((len(PHONES), n_mels)).astype(np.float32)
    pitch_sig = sig.standard_normal(len(PHONES)).astype(np.float32)
    energy_sig = sig.standard_normal(len(PHONES)).astype(np.float32)
    noise = spec.get("noise", 0.1)
    for kind in ("mel", "pitch", "energy", "duration"):
        os.makedirs(os.path.join(out_dir, kind), exist_ok=True)
    lines, total = [], 0
    for slot, j in enumerate(order):
        n, dur = deck[j]
        ids = rng.integers(0, len(PHONES), n)
        mel = np.repeat(mel_sig[ids], dur, axis=0)
        mel += noise * rng.standard_normal(mel.shape, dtype=np.float32)
        pitch = pitch_sig[ids] + noise * rng.standard_normal(n, dtype=np.float32)
        energy = energy_sig[ids] + noise * rng.standard_normal(n, dtype=np.float32)
        base = f"u{slot:05d}"
        for kind, arr in (("mel", mel), ("pitch", pitch), ("energy", energy),
                          ("duration", dur)):
            np.save(os.path.join(out_dir, kind, f"S-{kind}-{base}.npy"), arr)
        phones = " ".join(PHONES[i] for i in ids)
        lines.append(f"{base}|S|{{{phones}}}|utterance {slot}")
        total += int(dur.sum())
    with open(os.path.join(out_dir, "train.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.join(out_dir, "val.txt"), "w") as f:
        f.write("\n".join(lines[: spec.get("val_utterances", 8)]) + "\n")
    with open(os.path.join(out_dir, "speakers.json"), "w") as f:
        json.dump({"S": 0}, f)
    with open(os.path.join(out_dir, "stats.json"), "w") as f:
        json.dump({"pitch": [*spec["pitch_range"], 0.0, 1.0],
                   "energy": [*spec["energy_range"], 0.0, 1.0]}, f)
    return {"frames_per_cycle": total, "utterances": len(deck)}
