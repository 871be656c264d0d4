"""Long-form (chapter-length) synthesis: the audiobook workload.

The interactive lattice admits at most ``serve.src_buckets[-1]``
phonemes / ``serve.mel_buckets[-1]`` mel frames and 413s anything
longer.  This module opens the request class above that ceiling —
chapters whose service time is ~100x the interactive one — behind
``POST /synthesize/longform``, with two tiers:

**Tier (a), chunked (always available).**  A host-side chapter chunker
splits the text at sentence boundaries (``split_sentences``) and packs
sentences into utterances that each fit the interactive lattice
(``plan_chunks`` — the per-sentence G2P sequences are what is packed,
so the planned phoneme counts are exact, never re-estimated).  The
chunks are synthesized as a *deadline-sharing group* of long-form-class
requests through the existing batcher/fleet: every chunk carries the
chapter's arrival time and one shared ``deadline_ms`` override (the
group budget scales with the chunk count — ``serve.longform.
deadline_ms_per_chunk`` clamped to ``serve.fleet.max_deadline_ms``), so
the EDF router treats the whole chapter as one late-deadline unit that
never starves interactive traffic.  Prosodic continuity across the
seams comes from two mechanisms: the chapter's duration/pitch/energy
controls and resolved style are carried identically into every chunk
(no per-chunk drift), and the wavs are joined by an equal-power
crossfade (``Stitcher``) sized in mel frames
(``serve.longform.crossfade_frames``) — the same overlap-trim
philosophy as streaming.py, applied at the chunk seam.  Memory is
bounded by construction: at most ``serve.longform.group_depth`` chunk
requests are in flight ahead of the stitch point and the stitcher holds
only one crossfade tail, so the full chapter is never materialized
host-side (jaxlint JL019 polices the concatenate-the-chapter failure
mode structurally).

**Tier (b), ring (``serve.longform.mesh_seq > 1``).**  One coherent
chapter-length utterance is ONE program: ``RingTier`` compiles the
acoustic free-run with ``attention_impl="ring"``
(parallel/ring_attention.py — K/V blocks rotate around a ``seq``-axis
mesh with a streaming log-sum-exp merge) through the ProgramRegistry at
the dedicated ``serve.longform.{src,mel}_buckets`` above the
interactive lattice, inputs/outputs replicated and the shard_map inside
the attention doing the sequence split.  The resulting mel streams out
through the engine's precompiled vocoder windows (streaming.stream_wav)
— chapter-length output, interactive-sized vocoder programs, zero
steady-state compiles.

Tier selection happens at admission (``LongformService.admit``): ring
when configured, available, and the chapter fits a ring bucket; chunked
otherwise.  A ring-tier failure before the first emitted sample
degrades to the chunked tier (PR 9 style — counted in
``serve_longform_degraded_total``, driven in tests by the
``longform_ring_error@N`` fault kind).
"""

import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.faults import FaultPlan
from speakingstyle_tpu.obs import MetricsRegistry
from speakingstyle_tpu.serving import streaming
from speakingstyle_tpu.serving.engine import (
    SynthesisRequest,
    SynthesisResult,
    _fill_control,
    bucket_label,
)
from speakingstyle_tpu.serving.lattice import BucketLattice, RequestTooLarge
from speakingstyle_tpu.serving.resilience import InjectedFault
from speakingstyle_tpu.obs.locks import make_lock

__all__ = [
    "split_sentences",
    "plan_chunks",
    "Chunk",
    "Stitcher",
    "RingTier",
    "LongformPlan",
    "LongformService",
]


# ---------------------------------------------------------------------------
# chapter chunking
# ---------------------------------------------------------------------------

# sentence-final punctuation (ASCII + CJK + ellipsis), consumed together
# with the trailing whitespace; the punctuation stays with its sentence
_SENTENCE_SPLIT = re.compile(r"(?<=[.!?…。！？])\s+")


def split_sentences(text: str) -> List[str]:
    """Deterministic sentence-boundary split: break after ``.!?…。！？``
    followed by whitespace, keep the punctuation, strip and drop empty
    pieces.  Text with no sentence-final punctuation comes back as one
    sentence — the giant-sentence fallback in ``plan_chunks`` handles
    it."""
    if not text:
        return []
    return [p.strip() for p in _SENTENCE_SPLIT.split(text) if p.strip()]


@dataclass
class Chunk:
    """One lattice-sized utterance of the chapter."""

    index: int
    text: str
    sequence: np.ndarray  # [n] int32 phoneme ids, n <= the planned cap
    n_sentences: int = 1


def plan_chunks(
    text: str,
    encode: Callable[[str], np.ndarray],
    max_phonemes: int,
    max_chunks: int = 0,
) -> List[Chunk]:
    """Split ``text`` at sentence boundaries and greedily pack sentences
    into chunks of at most ``max_phonemes`` G2P ids each.

    The packing works on the per-sentence *phoneme sequences* (one
    ``encode`` call per sentence), and a chunk's sequence is the exact
    concatenation of its sentences' sequences — so the planned counts
    are the admitted counts, never an estimate that re-G2P could
    overflow.  A single sentence longer than ``max_phonemes`` has no
    boundary to split at: its sequence is hard-split into
    ``max_phonemes``-sized slices (the honest fallback — a mid-word seam
    beats a 413).  Empty/whitespace text plans zero chunks.
    ``max_chunks > 0`` bounds the chapter: exceeding it raises
    RequestTooLarge (the admission cap, reported as a structured 413).
    """
    if max_phonemes <= 0:
        raise ValueError(f"max_phonemes must be > 0, got {max_phonemes}")
    pieces: List[tuple] = []  # (sentence_text, [int ids])
    for sent in split_sentences(text):
        seq = np.asarray(encode(sent), np.int32)
        if seq.size == 0:
            continue
        if seq.size <= max_phonemes:
            pieces.append((sent, seq.tolist()))
        else:
            # one giant sentence: hard-split the phoneme sequence
            for off in range(0, seq.size, max_phonemes):
                pieces.append((sent, seq[off:off + max_phonemes].tolist()))
    chunks: List[Chunk] = []
    ids: List[int] = []
    texts: List[str] = []

    def flush():
        if ids:
            chunks.append(Chunk(
                index=len(chunks),
                text=" ".join(dict.fromkeys(texts)),
                sequence=np.asarray(ids, np.int32),
                n_sentences=len(texts),
            ))
            ids.clear()
            texts.clear()

    for sent, seq_ids in pieces:
        if ids and len(ids) + len(seq_ids) > max_phonemes:
            flush()
        ids.extend(seq_ids)
        texts.append(sent)
    flush()
    if max_chunks and len(chunks) > max_chunks:
        raise RequestTooLarge(
            f"chapter plans {len(chunks)} chunks, over the "
            f"serve.longform.max_chunks={max_chunks} admission cap "
            f"({max_phonemes * max_chunks} phonemes); split the request"
        )
    return chunks


# ---------------------------------------------------------------------------
# prosodic stitching
# ---------------------------------------------------------------------------


class Stitcher:
    """Equal-power crossfade joiner with bounded memory.

    ``feed`` one int16 chunk wav at a time; each call returns the newly
    emittable pieces (everything except the held-back crossfade tail),
    and ``finish`` flushes the final tail.  The only state carried
    between chunks is that tail (at most ``fade`` samples), so a
    chapter of any length stitches in O(one chunk) memory.

    At each seam the previous tail and the next head are mixed over an
    equal-power sin/cos ramp (constant perceived energy through the
    join).  ``seam_rms`` records, per seam, the RMS of the
    sample-to-sample first difference across the stitched join window
    (normalized to [-1, 1]) — the click detector the long-form tests
    (tests/test_longform.py) read.
    """

    def __init__(self, fade_samples: int, quality_check=None):
        if fade_samples < 0:
            raise ValueError(f"fade_samples must be >= 0, got {fade_samples}")
        self.fade = int(fade_samples)
        self._tail: Optional[np.ndarray] = None
        self._last_emitted: float = 0.0  # last sample before the seam
        self.seam_rms: List[float] = []
        # the longform choke point (obs/quality.py QualityGate.check
        # bound by LongformService): every emitted piece — crossfade
        # mixes included — is validated before it leaves the stitcher
        self.quality_check = quality_check

    def _note_seam(self, prev: float, mixed: np.ndarray, nxt: float) -> None:
        window = np.empty(mixed.size + 2, np.float32)
        window[0] = prev
        window[1:-1] = mixed
        window[-1] = nxt
        d = np.diff(window / 32768.0)
        self.seam_rms.append(float(np.sqrt(np.mean(d * d))))

    def feed(self, wav: np.ndarray) -> List[np.ndarray]:
        wav = np.asarray(wav, np.int16)
        if wav.size == 0:
            return []
        out: List[np.ndarray] = []
        if self._tail is not None:
            f = min(self._tail.size, wav.size, self.fade)
            if f > 0:
                # equal-power ramp: cos fades the old tail out while sin
                # fades the new head in; cos^2 + sin^2 = 1 keeps the
                # energy through the seam flat
                th = (np.arange(f, dtype=np.float32) + 0.5) * (np.pi / (2 * f))
                mixed_f = (
                    self._tail[-f:].astype(np.float32) * np.cos(th)
                    + wav[:f].astype(np.float32) * np.sin(th)
                )
                mixed = np.clip(mixed_f, -32768, 32767).astype(np.int16)
                if self._tail.size > f:
                    out.append(self._tail[:-f])
                    prev = float(self._tail[-f - 1])
                else:
                    prev = self._last_emitted
                nxt = float(wav[f]) if wav.size > f else float(mixed[-1])
                self._note_seam(prev, mixed_f, nxt)
                out.append(mixed)
                wav = wav[f:]
            else:
                # fade 0 (or an empty tail): butt joint, still metered
                if self._tail.size:
                    out.append(self._tail)
                    prev = float(self._tail[-1])
                else:
                    prev = self._last_emitted
                if wav.size:
                    self._note_seam(
                        prev, np.asarray([float(wav[0])], np.float32),
                        float(wav[1]) if wav.size > 1 else float(wav[0]),
                    )
        # hold back the next seam's tail; emit the rest
        if wav.size > self.fade:
            out.append(wav[:wav.size - self.fade])
            self._tail = wav[wav.size - self.fade:]
        else:
            self._tail = wav
        for piece in reversed(out):
            if piece.size:
                self._last_emitted = float(piece[-1])
                break
        pieces = [p for p in out if p.size]
        if self.quality_check is not None:
            for p in pieces:
                self.quality_check(p)
        return pieces

    def finish(self) -> List[np.ndarray]:
        tail, self._tail = self._tail, None
        pieces = [tail] if tail is not None and tail.size else []
        if self.quality_check is not None:
            for p in pieces:
                self.quality_check(p)
        return pieces


# ---------------------------------------------------------------------------
# tier (b): the seq-sharded ring-attention free-run
# ---------------------------------------------------------------------------


class RingTier:
    """Chapter-length acoustic free-run as ONE ring-attention program.

    Compiles the same inference function the engine serves, but with a
    model built at ``attention_impl="ring"`` over a ``seq``-axis mesh
    (``serve.longform.mesh_seq`` devices) and at the dedicated long-form
    buckets — batch is always 1 (a chapter is not coalesced).  Inputs
    and outputs are replicated (``PartitionSpec()``); the shard_map
    inside the attention layers performs the sequence split, so the
    host-side staging/dispatch discipline is identical to the engine's
    (pool leases, explicit transfer, mel host readback).  All compiles
    flow through the shared ProgramRegistry and mint ProgramCards
    (``kind=acoustic_ring``) with their mesh geometry, visible at
    ``GET /debug/programs``.
    """

    def __init__(
        self,
        cfg: Config,
        variables: Dict,
        engine,  # SynthesisEngine: shares pool, vocoder windows, style
        program_registry=None,
        registry: Optional[MetricsRegistry] = None,
    ):
        import dataclasses as dc

        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from speakingstyle_tpu.models.factory import build_model
        from speakingstyle_tpu.parallel.mesh import make_seq_mesh

        lf = cfg.serve.longform
        if lf.mesh_seq < 2:
            raise ValueError(
                "RingTier needs serve.longform.mesh_seq >= 2 "
                f"(got {lf.mesh_seq}); the chunked tier serves smaller "
                "deployments"
            )
        self.cfg = cfg
        self.engine = engine
        self.registry = registry if registry is not None else engine.registry
        self.program_registry = (
            program_registry if program_registry is not None
            else engine.program_registry
        )
        self.mesh = make_seq_mesh(lf.mesh_seq)
        # ring requires f32 attention softmax (the streaming log-sum-exp
        # merge is an f32 contract); forcing it here keeps one model
        # YAML serving both tiers
        ring_cfg = dc.replace(cfg, model=dc.replace(
            cfg.model, attention_impl="ring",
            attention_softmax_dtype="float32",
        ))
        self.lattice = BucketLattice(
            [1], list(lf.src_buckets), list(lf.mel_buckets)
        )
        n_position = max(
            self.lattice.max_mel, self.lattice.max_src, cfg.model.max_seq_len
        ) + 1
        self.model = build_model(
            ring_cfg, n_position=n_position, seq_mesh=self.mesh
        )
        self._repl = NamedSharding(self.mesh, PartitionSpec())
        # the tier's own replicated placement on the seq mesh — the
        # engine's copy may live on a different (dp, tp) mesh
        self.variables = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._repl), variables
        )
        self._use_style = cfg.model.use_reference_encoder
        self._film_dim = cfg.model.reference_encoder.encoder_hidden
        pp = cfg.preprocess.preprocessing
        self._pitch_axis = (
            "src" if pp.pitch.feature == "phoneme_level" else "mel"
        )
        self._energy_axis = (
            "src" if pp.energy.feature == "phoneme_level" else "mel"
        )
        self._programs: Dict[object, object] = {}
        self._lock = make_lock("RingTier._lock")
        self._ring_hist = self.registry.histogram(
            "serve_longform_ring_seconds",
            help="wall time of one ring-attention chapter free-run "
                 "(staging + dispatch + mel host readback)",
        )

    @property
    def max_src(self) -> int:
        return self.lattice.max_src

    @property
    def max_mel(self) -> int:
        return self.lattice.max_mel

    def _ring_fn(self, t_mel: int):
        def fn(variables, speakers, texts, src_lens, gammas, betas,
               p_control, e_control, d_control):
            out = self.model.apply(
                variables,
                speakers=speakers,
                texts=texts,
                src_lens=src_lens,
                mels=None,
                mel_lens=None,
                max_mel_len=t_mel,
                p_control=p_control,
                e_control=e_control,
                d_control=d_control,
                gammas=gammas if self._use_style else None,
                betas=betas if self._use_style else None,
                deterministic=True,
            )
            keep = ("mel_postnet", "mel_lens", "durations",
                    "pitch_prediction", "energy_prediction")
            return {k: out[k] for k in keep}
        return fn

    def _ctl_len(self, axis: str, bucket) -> int:
        return bucket.l_src if axis == "src" else bucket.t_mel

    def precompile(self) -> float:
        """AOT-compile every long-form lattice point (JL008-sanctioned
        startup loop); returns wall seconds spent."""
        t0 = time.monotonic()
        for bucket in self.lattice.points():
            self._compile(bucket)
        return time.monotonic() - t0

    def _compile(self, bucket):
        import jax
        import jax.numpy as jnp

        l, t = bucket.l_src, bucket.t_mel
        s = jax.ShapeDtypeStruct
        d = self._film_dim
        args = (
            self.variables,
            s((1,), jnp.int32),
            s((1, l), jnp.int32),
            s((1,), jnp.int32),
            s((1, 1, d), jnp.float32),
            s((1, 1, d), jnp.float32),
            s((1, self._ctl_len(self._pitch_axis, bucket)), jnp.float32),
            s((1, self._ctl_len(self._energy_axis, bucket)), jnp.float32),
            s((1, l), jnp.float32),
        )
        donate = tuple(range(1, 9)) if self.cfg.serve.donate_buffers else ()
        label = bucket_label(bucket)
        name = f"acoustic_ring:{label}"
        var_sh = jax.tree_util.tree_map(lambda _: self._repl, self.variables)
        self._programs[bucket] = self.program_registry.compile(
            self._ring_fn(t), args,
            name=name,
            donate_argnums=donate,
            in_shardings=(var_sh,) + (self._repl,) * 8,
            out_shardings=self._repl,
            labels={
                "kind": "acoustic_ring", "bucket": label,
                "mesh": f"seq{self.cfg.serve.longform.mesh_seq}",
            },
        )

    def synthesize(self, req: SynthesisRequest) -> SynthesisResult:
        """One chapter, one program: pad into the covering long-form
        bucket, execute the ring free-run, return a mel-only result
        (``wav=None`` — the caller streams it through the engine's
        precompiled vocoder windows)."""
        import jax

        n = int(len(req.sequence))
        need = n * self.cfg.serve.frames_per_phoneme
        bucket = self.lattice.cover(1, n, need)
        style = req.style
        if self._use_style and style is None:
            if req.ref_mel is None:
                raise ValueError(
                    f"request {req.id!r} carries neither style vectors "
                    "nor a ref_mel"
                )
            if self.engine.style is None:
                raise ValueError(
                    f"request {req.id!r} carries a ref_mel but the "
                    "engine has no style service to encode it"
                )
            # cache-first through the shared StyleService (content-
            # addressed: a chapter re-using a chunked-tier style costs
            # zero encoder work)
            style = self.engine.style.encode_mels([req.ref_mel])[0]
        with self._lock:
            if bucket not in self._programs:
                self._compile(bucket)
        t0 = time.monotonic()
        leases: List[np.ndarray] = []
        dev: Dict[str, object] = {}
        synced = False

        def staging(shape, dtype=np.float32, fill: float = 0) -> np.ndarray:
            buf = self.engine.pool.acquire(shape, dtype, fill)
            leases.append(buf)
            return buf

        try:
            speakers = staging((1,), np.int32)
            texts = staging((1, bucket.l_src), np.int32)
            src_lens = staging((1,), np.int32)
            gammas = staging((1, 1, self._film_dim))
            betas = staging((1, 1, self._film_dim))
            speakers[0] = req.speaker
            texts[0, :n] = req.sequence
            src_lens[0] = n
            if style is not None:
                gammas[0, 0] = style.gamma
                betas[0, 0] = style.beta
            arrays = {
                "speakers": speakers,
                "texts": texts,
                "src_lens": src_lens,
                "gammas": gammas,
                "betas": betas,
                "p_control": _fill_control([req.p_control], staging(
                    (1, self._ctl_len(self._pitch_axis, bucket)), fill=1)),
                "e_control": _fill_control([req.e_control], staging(
                    (1, self._ctl_len(self._energy_axis, bucket)), fill=1)),
                "d_control": _fill_control([req.d_control], staging(
                    (1, bucket.l_src), fill=1)),
            }
            dev = {
                k: jax.device_put(v, self._repl) for k, v in arrays.items()
            }
            out = self._programs[bucket](
                self.variables, dev["speakers"], dev["texts"],
                dev["src_lens"], dev["gammas"], dev["betas"],
                dev["p_control"], dev["e_control"], dev["d_control"],
            )
            mel_host = np.asarray(out["mel_postnet"])
            synced = True
        finally:
            if leases and not synced and dev:
                try:
                    jax.block_until_ready(list(dev.values()))
                except Exception:  # jaxlint: disable=JL007
                    pass  # donated/failed arrays: nothing left reading
            for buf in leases:
                self.engine.pool.release(buf)
        mel_len = int(np.asarray(out["mel_lens"])[0])
        durations = np.asarray(out["durations"])
        pitch = np.asarray(out["pitch_prediction"])
        energy = np.asarray(out["energy_prediction"])
        self._ring_hist.observe(time.monotonic() - t0)
        p_len = n if self._pitch_axis == "src" else mel_len
        e_len = n if self._energy_axis == "src" else mel_len
        return SynthesisResult(
            id=req.id,
            raw_text=req.raw_text,
            mel=mel_host[0, :mel_len],
            mel_len=mel_len,
            wav=None,
            durations=durations[0, :n],
            pitch_prediction=pitch[0, :p_len],
            energy_prediction=energy[0, :e_len],
            src_len=n,
            bucket=bucket,
            batch_rows=1,
            style_degraded=req.style_degraded,
        )


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------


@dataclass
class LongformPlan:
    """One admitted chapter: the chunk plan plus everything resolved
    once for the whole request (style, speaker, controls, tier)."""

    req_id: str
    chunks: List[Chunk]
    tier: str  # "ring" | "chunked" — mutated to "chunked" on degradation
    deadline_ms: float  # shared group budget (already clamped)
    total_phonemes: int
    speaker: int = 0
    style: object = None
    ref_mel: Optional[np.ndarray] = None
    style_degraded: bool = False
    p_control: float = 1.0
    e_control: float = 1.0
    d_control: float = 1.0
    arrival: float = field(default_factory=time.monotonic)

    def info(self) -> Dict:
        return {
            "tier": self.tier,
            "chunks": len(self.chunks),
            "phonemes": self.total_phonemes,
            "deadline_ms": self.deadline_ms,
        }


class LongformService:
    """Admission + orchestration for ``POST /synthesize/longform``.

    ``admit`` parses and validates the payload, runs the chapter
    chunker, resolves style/speaker/controls ONCE for the whole chapter
    and selects the tier; ``stream`` yields int16 wav pieces with
    bounded memory on either tier.  The service never compiles in the
    request path: ring programs precompile at startup, chunk requests
    ride the engine's interactive lattice.
    """

    def __init__(
        self,
        cfg: Config,
        frontend,               # TextFrontend (duck-typed; serving/server.py)
        backend,                # ContinuousBatcher or FleetRouter: submit()
        engine=None,            # SynthesisEngine for ring-tier vocoding
        ring: Optional[RingTier] = None,
        fault_plan: Optional[FaultPlan] = None,
        registry: Optional[MetricsRegistry] = None,
        events=None,
        quality=None,           # obs/quality.QualityGate (None = unchecked)
    ):
        self.cfg = cfg
        self.frontend = frontend
        self.backend = backend
        self.engine = engine
        self.ring = ring
        self.fault_plan = fault_plan
        self.quality = quality
        if registry is not None:
            self.registry = registry
        elif engine is not None:
            self.registry = engine.registry
        else:
            self.registry = MetricsRegistry()
        self.events = events
        fleet = cfg.serve.fleet
        # long-form chunks ride the lowest-urgency configured class: a
        # dedicated "long_form" class when the deployment defines one,
        # else "batch", else the default
        if "long_form" in fleet.class_deadline_ms:
            self.klass = "long_form"
        elif "batch" in fleet.class_deadline_ms:
            self.klass = "batch"
        else:
            self.klass = fleet.default_class
        self._ring_attempts = 0
        self._ring_lock = make_lock("LongformService._ring_lock")
        self._chunks_ctr = self.registry.counter(
            "serve_longform_chunks_total",
            help="chapter chunks synthesized by the chunked tier",
        )
        self._degraded_ctr = self.registry.counter(
            "serve_longform_degraded_total",
            help="ring-tier failures degraded to the chunked tier",
        )
        self._seam_hist = self.registry.histogram(
            "serve_longform_seam_rms",
            help="per-seam RMS of the first difference across the "
                 "stitched join window (normalized; the click detector)",
        )
        self._ttfa_hist = self.registry.histogram(
            "serve_longform_ttfa_seconds",
            help="chapter admission -> first stitched wav piece ready",
        )

    # -- admission -----------------------------------------------------------

    @property
    def chunk_phoneme_cap(self) -> int:
        """Largest per-chunk phoneme count the interactive lattice
        admits: bounded by the src axis AND by the mel axis via
        frames_per_phoneme."""
        serve = self.cfg.serve
        return min(
            serve.src_buckets[-1],
            serve.mel_buckets[-1] // serve.frames_per_phoneme,
        )

    def _controls(self, payload: Dict):
        vals = []
        for key in ("pitch_control", "energy_control", "duration_control"):
            v = payload.get(key, 1.0)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(
                    f"{key} must be a scalar on /synthesize/longform "
                    "(per-word lists cannot span chapter chunks)"
                )
            vals.append(float(v))
        return vals

    def admit(self, req_id: str, payload: Dict) -> LongformPlan:
        """Validate + plan one chapter.  Raises ValueError (400) on a
        malformed payload and RequestTooLarge (413) past the
        ``max_chunks`` admission cap."""
        text = payload.get("text")
        if not text or not isinstance(text, str):
            raise ValueError('payload must carry a non-empty "text" string')
        lf = self.cfg.serve.longform
        want = payload.get("tier", lf.tier)
        if want not in ("auto", "chunked", "ring"):
            raise ValueError(
                f'tier must be "auto"|"chunked"|"ring", got {want!r}'
            )
        p_c, e_c, d_c = self._controls(payload)
        style_vec, ref_mel, degraded = self.frontend.resolve_style(payload)
        spec = payload.get("speaker_id", payload.get("speaker"))
        speaker = self.frontend.speaker(spec) if spec is not None else 0
        if style_vec is not None and getattr(style_vec, "speaker", None) \
                is not None:
            bound = self.frontend.speaker(style_vec.speaker)
            if spec is None:
                speaker = bound
            elif speaker != bound:
                raise ValueError(
                    f"style is bound to speaker {style_vec.speaker!r}; "
                    "request named a different speaker"
                )
        chunks = plan_chunks(
            text, self.frontend.sequence,
            self.chunk_phoneme_cap, lf.max_chunks,
        )
        if not chunks:
            raise ValueError("text contains nothing synthesizable")
        total = int(sum(c.sequence.size for c in chunks))
        fleet = self.cfg.serve.fleet
        budget = min(
            len(chunks) * lf.deadline_ms_per_chunk, fleet.max_deadline_ms
        )
        tier = "chunked"
        if want in ("auto", "ring") and self._ring_fits(total):
            tier = "ring"
        plan = LongformPlan(
            req_id=req_id,
            chunks=chunks,
            tier=tier,
            deadline_ms=budget,
            total_phonemes=total,
            speaker=speaker,
            style=style_vec,
            ref_mel=ref_mel,
            style_degraded=degraded,
            p_control=p_c,
            e_control=e_c,
            d_control=d_c,
        )
        self.registry.counter(
            "serve_longform_requests_total", labels={"tier": tier},
            help="long-form chapters admitted, by selected tier",
        ).inc()
        if self.events is not None:
            self.events.emit("longform_admit", req_id=req_id, **plan.info())
        return plan

    def _ring_fits(self, total_phonemes: int) -> bool:
        if self.ring is None or self.engine is None \
                or self.engine.vocoder is None:
            return False
        fpp = self.cfg.serve.frames_per_phoneme
        return (total_phonemes <= self.ring.max_src
                and total_phonemes * fpp <= self.ring.max_mel)

    # -- synthesis -----------------------------------------------------------

    def stream(self, plan: LongformPlan) -> Iterator[np.ndarray]:
        """Yield the chapter's int16 wav pieces in order, bounded
        memory.  Ring-tier failures before the first piece degrade to
        the chunked tier; later faults abort the stream (the chunked
        HTTP body ends without its terminal chunk — same contract as
        /synthesize/stream)."""
        if plan.tier == "ring":
            try:
                result = self._ring_result(plan)
            except Exception as e:
                self._degraded_ctr.inc()
                self.registry.counter(
                    "serve_longform_requests_total",
                    labels={"tier": "chunked"},
                    help="long-form chapters admitted, by selected tier",
                ).inc()
                if self.events is not None:
                    self.events.emit(
                        "longform_degraded", req_id=plan.req_id,
                        error=type(e).__name__,
                    )
                plan.tier = "chunked"
            else:
                yield from self._ring_stream(plan, result)
                return
        yield from self._chunked(plan)

    def _ring_result(self, plan: LongformPlan) -> SynthesisResult:
        with self._ring_lock:
            self._ring_attempts += 1
            attempt = self._ring_attempts
        if self.fault_plan is not None and self.fault_plan.fire(
            "longform_ring_error", attempt
        ):
            raise InjectedFault(
                f"injected longform_ring_error at ring attempt {attempt}"
            )
        ids: List[int] = []
        for c in plan.chunks:
            ids.extend(c.sequence.tolist())
        req = SynthesisRequest(
            id=plan.req_id,
            sequence=np.asarray(ids, np.int32),
            ref_mel=plan.ref_mel,
            style=plan.style,
            speaker=plan.speaker,
            raw_text="",
            p_control=plan.p_control,
            e_control=plan.e_control,
            d_control=plan.d_control,
            arrival=plan.arrival,
            stream=True,
            style_degraded=plan.style_degraded,
        )
        return self.ring.synthesize(req)

    def _ring_stream(
        self, plan: LongformPlan, result: SynthesisResult
    ) -> Iterator[np.ndarray]:
        fleet = self.cfg.serve.fleet
        overlap = streaming.resolve_overlap(
            fleet.stream_overlap, self.engine.vocoder[0]
        )
        # A ring chapter's mel can dwarf the serve-tier mel buckets, so
        # every overlap-padded vocode window must itself fit the
        # engine's vocoder lattice: window + 2*overlap <= max_mel.
        window = min(
            fleet.stream_window, self.engine.lattice.max_mel - 2 * overlap
        )
        if window < 1:
            raise ValueError(
                f"ring stream overlap {overlap} leaves no room inside "
                f"the largest vocoder bucket {self.engine.lattice.max_mel}"
                "; enlarge serve.mel_buckets or set fleet.stream_overlap"
            )
        first = True
        for wav in streaming.stream_wav(
            self.engine, result, window, overlap, fleet.stream_depth,
        ):
            if first:
                self._ttfa_hist.observe(time.monotonic() - plan.arrival)
                first = False
            yield wav
        if self.events is not None:
            self.events.emit(
                "longform_done", req_id=plan.req_id, tier="ring",
                chunks=len(plan.chunks), mel_len=result.mel_len,
            )

    def _remaining(self, plan: LongformPlan) -> float:
        fleet = self.cfg.serve.fleet
        deadline = plan.arrival + (
            plan.deadline_ms + fleet.deadline_grace_ms
        ) / 1e3
        return max(0.001, deadline - time.monotonic())

    def _chunk_request(self, plan: LongformPlan, c: Chunk) -> SynthesisRequest:
        return SynthesisRequest(
            id=f"{plan.req_id}.c{c.index:03d}",
            sequence=c.sequence,
            ref_mel=plan.ref_mel,
            style=plan.style,
            speaker=plan.speaker,
            raw_text=c.text,
            p_control=plan.p_control,
            e_control=plan.e_control,
            d_control=plan.d_control,
            # the deadline-sharing group: every chunk carries the
            # chapter's arrival and ONE shared budget, so the EDF heap
            # orders the whole chapter as a unit
            arrival=plan.arrival,
            priority=self.klass,
            deadline_ms=plan.deadline_ms,
            style_degraded=plan.style_degraded,
        )

    def _quality_check_for(self, plan: LongformPlan):
        """The stitcher's choke-point binding: every emitted piece is
        validated under the chapter's traffic class (obs/quality.py).
        None when the service has no gate — stitching is unchecked."""
        if self.quality is None:
            return None

        def check(wav):
            return self.quality.check(
                wav, klass=self.klass, source="longform",
                req_id=plan.req_id,
            )

        return check

    def _chunked(self, plan: LongformPlan) -> Iterator[np.ndarray]:
        lf = self.cfg.serve.longform
        hop = self.cfg.preprocess.preprocessing.stft.hop_length
        stitcher = Stitcher(
            lf.crossfade_frames * hop,
            quality_check=self._quality_check_for(plan),
        )
        pending: "deque" = deque()  # submitted, uncollected futures
        it = iter(plan.chunks)
        first = True
        n_seams_noted = 0
        try:
            exhausted = False
            while not exhausted or pending:
                while not exhausted and len(pending) < lf.group_depth:
                    c = next(it, None)
                    if c is None:
                        exhausted = True
                        break
                    pending.append(
                        self.backend.submit(self._chunk_request(plan, c))
                    )
                if not pending:
                    break
                result = pending.popleft().result(
                    timeout=self._remaining(plan)
                )
                if result.wav is None:
                    raise ValueError(
                        "long-form synthesis requires a vocoder engine"
                    )
                self._chunks_ctr.inc()
                for piece in stitcher.feed(result.wav):
                    if first:
                        self._ttfa_hist.observe(
                            time.monotonic() - plan.arrival
                        )
                        first = False
                    yield piece
                for rms in stitcher.seam_rms[n_seams_noted:]:
                    self._seam_hist.observe(rms)
                    n_seams_noted += 1
            for piece in stitcher.finish():
                yield piece
        finally:
            # consumer hung up or a chunk failed: the uncollected
            # futures would otherwise pin their results — cancel what
            # has not dispatched and let the rest resolve unobserved
            while pending:
                pending.popleft().cancel()
        if self.events is not None:
            self.events.emit(
                "longform_done", req_id=plan.req_id, tier="chunked",
                chunks=len(plan.chunks), seams=n_seams_noted,
                seam_rms_max=max(stitcher.seam_rms, default=0.0),
            )
