"""Quality-tiered serving: class->tier routing over canary-gated tiers.

ROADMAP item 2, the routing half. A **tier** is a (model, precision)
pair named ``<model>-<precision>`` — ``teacher-f32`` is the
full-precision reference, ``teacher-bf16``/``teacher-int8`` are the
precision lattice's cheaper programs over the same weights, and
``student-*`` tiers serve the distilled fast acoustic model
(training/distill.py) registered as a second model version. Each tier
is a full ``FleetRouter`` (or ``ClusterRouter``) whose engines compile
the lattice at the tier's precision; the ``TierRouter`` facade in front
of them is what the HTTP server talks to, so "mixed-tier fleet
behind one router" is literally one object with the router surface.

The quality door is the PR-13 canary discipline re-aimed: before a tier
joins the routing table, ``tier_gate`` replays the deterministic golden
set (lifecycle.make_golden_set — the same corpus the rollout canary
uses) through the candidate tier AND the teacher-f32 anchor, and the
tier ships only if its golden-set mel-L2 against the teacher holds
under ``serve.tiers.tier_tolerance`` (plus all-finite, the broken-cast
detector). A failed gate does not 404 a traffic class: ``tier_for``
falls back to ``serve.tiers.default_tier`` (the teacher), so routing
degrades in quality budget, never in availability.

Metrics: ``serve_tier_dispatch_total{tier=}`` counts routed submits per
tier, ``serve_tier_canary_total{tier=,outcome=}`` counts gate verdicts,
and ``serve_tier_mel_l2{tier=}`` gauges each shipped tier's measured
golden-set distance — the two axes of the quality-vs-speed frontier.
"""

import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

import numpy as np

from speakingstyle_tpu.obs import MetricsRegistry
from speakingstyle_tpu.parallel.registry import PRECISIONS
from speakingstyle_tpu.serving.engine import SynthesisRequest, SynthesisResult
from speakingstyle_tpu.serving.lifecycle import make_golden_set

__all__ = [
    "TierGateResult",
    "TierRouter",
    "TierSpec",
    "parse_tier",
    "tier_gate",
]


@dataclass(frozen=True)
class TierSpec:
    """One parsed tier name: which weights and at what precision."""

    name: str        # "teacher-f32", "student-int8", ...
    model: str       # "teacher" | "student"
    precision: str   # registry.PRECISIONS member


def parse_tier(name: str) -> TierSpec:
    """``<model>-<precision>`` -> TierSpec (the TiersConfig validator
    enforces the same grammar, so config-sourced names never raise)."""
    model, sep, precision = name.partition("-")
    if not sep or model not in ("teacher", "student") \
            or precision not in PRECISIONS:
        raise ValueError(
            f"tier name must be '<model>-<precision>' with model in "
            f"(teacher, student) and precision in {PRECISIONS}, got {name!r}"
        )
    return TierSpec(name=name, model=model, precision=precision)


@dataclass
class TierGateResult:
    """Verdict of one golden-set quality gate."""

    tier: str
    mel_l2: float          # RMS mel distance vs the teacher anchor
    tolerance: float
    shipped: bool
    detail: str
    gate_ms: float = 0.0

    def as_dict(self) -> Dict:
        return {
            "tier": self.tier,
            "mel_l2": self.mel_l2,
            "tolerance": self.tolerance,
            "shipped": self.shipped,
            "detail": self.detail,
            "gate_ms": round(self.gate_ms, 3),
        }


def tier_gate(candidate_engine, teacher_engine, cfg, tier: str,
              tolerance: Optional[float] = None) -> TierGateResult:
    """Replay the golden set through candidate and teacher engines and
    gate the tier on golden-set mel-L2 (RMS over the overlapping mel
    prefix — duration predictors of a student or a quantized teacher may
    legitimately disagree on length; the gate measures spectral damage,
    not retraining deltas) plus all-finite.

    Both engines run the probes directly (``engine.run``, no router) —
    the same seeded corpus and batch shape as the rollout canary, so the
    gate itself performs zero steady-state compiles on a precompiled
    lattice.
    """
    tiers = cfg.serve.tiers
    tol = float(tolerance if tolerance is not None else tiers.tier_tolerance)
    spec = parse_tier(tier)
    golden = make_golden_set(cfg, tiers.golden_set_size, tiers.golden_seed)
    t0 = time.monotonic()
    cand_reqs = []
    for i, g in enumerate(golden):
        # re-mint the candidate's probes so the teacher replay keeps its
        # own pristine copies (run() mutates style_degraded in place)
        cand_reqs.append(SynthesisRequest(
            id=f"{g.id}.cand",
            sequence=g.sequence.copy(),
            ref_mel=None if g.ref_mel is None else g.ref_mel.copy(),
            precision=spec.precision,
        ))
    cand = candidate_engine.run(cand_reqs)
    anchor = teacher_engine.run(list(golden))
    worst = 0.0
    for i, (c, a) in enumerate(zip(cand, anchor)):
        c_mel = np.asarray(c.mel, dtype=np.float32)
        a_mel = np.asarray(a.mel, dtype=np.float32)
        if not np.all(np.isfinite(c_mel)):
            return TierGateResult(
                tier=tier, mel_l2=float("inf"), tolerance=tol,
                shipped=False, detail=f"golden{i}: non-finite tier output",
                gate_ms=(time.monotonic() - t0) * 1e3,
            )
        t = min(c_mel.shape[0], a_mel.shape[0])
        if t == 0:
            return TierGateResult(
                tier=tier, mel_l2=float("inf"), tolerance=tol,
                shipped=False, detail=f"golden{i}: empty tier output",
                gate_ms=(time.monotonic() - t0) * 1e3,
            )
        worst = max(worst, float(
            np.sqrt(np.mean(np.square(c_mel[:t] - a_mel[:t])))
        ))
    shipped = worst <= tol
    detail = (
        f"{len(golden)} golden requests, worst mel_l2 {worst:.4g} "
        f"{'within' if shipped else 'EXCEEDS'} tolerance {tol:.4g}"
    )
    return TierGateResult(
        tier=tier, mel_l2=worst, tolerance=tol, shipped=shipped,
        detail=detail, gate_ms=(time.monotonic() - t0) * 1e3,
    )


class TierRouter:
    """One router surface over N per-tier routers, routed by class.

    ``add_tier(name, router, gate=...)`` registers a tier; a gate result
    with ``shipped=False`` keeps the tier's router alive but OUT of the
    routing table (its traffic classes fall back to ``default_tier``).
    Everything the facade does not override — the model-lifecycle
    surface, autoscaler signals, ``wait_ready`` — delegates to the
    default tier's router, so the HTTP server and the RolloutManager
    drive a TierRouter exactly like a FleetRouter.
    """

    def __init__(self, cfg, registry: Optional[MetricsRegistry] = None):
        tiers = cfg.serve.tiers
        self.cfg = cfg
        self.tiers_cfg = tiers
        self.registry = registry if registry is not None else MetricsRegistry()
        self.default_tier = tiers.default_tier
        self._routers: Dict[str, object] = {}
        self._gates: Dict[str, TierGateResult] = {}

    # -- tier registry ------------------------------------------------------

    def add_tier(self, name: str, router,
                 gate: Optional[TierGateResult] = None) -> None:
        """Register one tier's router. ``gate=None`` means ungated
        (the default tier — the anchor gates itself by identity)."""
        parse_tier(name)
        self._routers[name] = router
        if gate is not None:
            self._gates[name] = gate
            self.registry.counter(
                "serve_tier_canary_total",
                labels={"tier": name,
                        "outcome": "shipped" if gate.shipped else "failed"},
                help="tier quality-gate verdicts (golden-set mel_l2 vs "
                     "the teacher anchor under serve.tiers.tier_tolerance)",
            ).inc()
            self.registry.gauge(
                "serve_tier_mel_l2", labels={"tier": name},
                help="measured golden-set mel_l2 of this tier vs the "
                     "teacher-f32 anchor (the gate's number)",
            ).set(gate.mel_l2)

    def tiers(self) -> List[str]:
        return sorted(self._routers)

    def shipped(self, name: str) -> bool:
        """A tier serves traffic only if it exists and its gate passed
        (no gate recorded = ungated = shipped: the anchor's case)."""
        if name not in self._routers:
            return False
        gate = self._gates.get(name)
        return gate is None or gate.shipped

    def gate_result(self, name: str) -> Optional[TierGateResult]:
        return self._gates.get(name)

    def tier_for(self, klass: Optional[str]) -> str:
        """class -> shipped tier name, falling back to the default tier
        when the class is unmapped or its tier failed the quality gate
        (routing degrades in quality budget, never in availability)."""
        klass = klass or self.cfg.serve.fleet.default_class
        name = self.tiers_cfg.class_tier.get(klass, self.default_tier)
        if not self.shipped(name):
            name = self.default_tier
        return name

    def routing_table(self) -> Dict[str, str]:
        """The effective class->tier map (fallbacks applied) — the
        /healthz tier block."""
        classes = set(self.cfg.serve.fleet.class_deadline_ms)
        classes.update(self.tiers_cfg.class_tier)
        return {k: self.tier_for(k) for k in sorted(classes)}

    def router_for(self, name: str):
        return self._routers[name]

    @property
    def _default_router(self):
        return self._routers[self.default_tier]

    # -- the router surface -------------------------------------------------

    def submit(self, request: SynthesisRequest):
        """Route one request to its class's tier: stamp the tier's
        precision onto the request (the engine picks the param tree and
        program from it) and delegate to that tier's router."""
        tier = self.tier_for(request.priority)
        spec = parse_tier(tier)
        request.precision = spec.precision
        self.registry.counter(
            "serve_tier_dispatch_total", labels={"tier": tier},
            help="requests routed to each quality tier",
        ).inc()
        return self._routers[tier].submit(request)

    def stream(self, result: SynthesisResult,
               arrival: Optional[float] = None) -> Iterator[np.ndarray]:
        """Stream continuations route by the tier stamped on the result
        (the producing tier's replica holds the mel's precision lattice)."""
        tier = result.tier or self.default_tier
        return self._routers[tier].stream(result, arrival)

    def ready(self) -> bool:
        """The facade is ready when the DEFAULT tier is (it is every
        class's fallback); other tiers warming merely narrows routing."""
        return self._default_router.ready()

    def wait_ready(self, timeout: float = 120.0,
                   n: Optional[int] = None) -> bool:
        return self._default_router.wait_ready(timeout, n)

    def states(self) -> Dict[str, Dict[int, str]]:
        """Per-tier replica state maps (tier -> {index: state})."""
        return {name: r.states() for name, r in sorted(self._routers.items())}

    def engines(self) -> List:
        out = []
        for _, r in sorted(self._routers.items()):
            out.extend(r.engines())
        return out

    def close(self, flush: bool = True, timeout: float = 30.0) -> None:
        for r in self._routers.values():
            r.close(flush=flush, timeout=timeout)

    def __getattr__(self, attr):
        # everything else (model_version, rollout_active, pending_depth,
        # fault_plan, lattice, ...) reads through to the default tier's
        # router — the facade is a FleetRouter wherever it isn't a map
        return getattr(self._default_router, attr)

    def __enter__(self) -> "TierRouter":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
