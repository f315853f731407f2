"""Backend registry — one recurrence (``repro.core.spec.DPSpec``), many
engines.

Each execution backend registers

  * a :class:`Capabilities` declaration — which distances, reductions
    and banding it supports, which result ``outputs`` it can fulfill
    (``repro.core.result.ALL_OUTPUTS``), whether it is differentiable /
    exact, and what device it needs — and
  * an ``execute(spec, plan)`` entry point taking the resolved
    :class:`~repro.core.spec.DPSpec` and an :class:`ExecutionPlan`
    (queries, reference, requested sweep outputs, dispatch options)
    and returning a typed :class:`~repro.core.result.SDTWResult`.

``repro.sdtw`` (core.api) then becomes a thin
resolve-spec → :func:`resolve` → ``backend.execute`` path, and callers
get capability errors ("backend 'kernel' does not support soft-min
... use one of ['engine', ...]") instead of silently-wrong numbers —
the same loud error covers output requests a backend cannot fulfill
("backend 'quantized' does not support output(s) ['start'] ...").

The builtin backends (ref / engine / kernel / quantized / distributed,
plus the ``soft`` alias for engine-with-soft-min) are registered lazily
on first registry access so importing this module stays cheap and free
of Pallas imports.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Mapping

from repro import obs
from repro.core.result import DEFAULT_OUTPUTS, normalize_outputs
from repro.core.spec import DPSpec

log = logging.getLogger(__name__)

_BASE_OUTPUTS = frozenset(DEFAULT_OUTPUTS)          # every backend: cost+end


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend can execute. Frozen: declared once at register."""

    distances: frozenset
    reductions: frozenset
    banding: bool = True
    differentiable: bool = False   # NaN-free gradients under softmin specs
    per_query_reference: bool = True   # accepts a (B, N) reference batch
    exact: bool = True             # reproduces the spec'd recurrence (the
    #                                quantized backend approximates it)
    outputs: frozenset = _BASE_OUTPUTS
    #   which SDTWResult fields a request routed at this backend can be
    #   fulfilled with (repro.core.result.ALL_OUTPUTS): every backend
    #   produces "cost"/"end"; "start" means matched-window start
    #   pointers propagate through the SAME sweep (hard-min specs only);
    #   "path" rides on "start" (Hirschberg traceback above the sweep);
    #   "soft_alignment" needs a differentiable backward underneath
    #   (jax.grad through the cost-matrix sweep, or the kernel's fused
    #   reverse sweep; soft-min specs only)
    families: frozenset = frozenset({"sdtw"})
    #   recurrence families (repro.core.spec.FAMILIES) the backend
    #   executes.  Default sdtw-only: a backend must OPT IN to a family
    #   — auto-selection can therefore never silently downgrade a
    #   family request onto a backend that would run the sdtw
    #   recurrence instead.
    window_families: frozenset = frozenset({"sdtw"})
    #   families the "start" output is served for.  Global families
    #   (twed/erp) have trivial starts (column 0, NO_WINDOW when the
    #   band blocks the corner); the local family has no window lane
    #   anywhere yet.
    multivariate_outputs: frozenset = frozenset()
    #   the sweep outputs served on multivariate (B, M, D) inputs of
    #   the sdtw family; empty: the backend declines them
    multivariate_reductions: frozenset = frozenset()
    #   the reductions served on multivariate inputs
    device: str = "any"            # human-readable requirement
    notes: str = ""

    def unsupported_reason(self, spec: DPSpec, outputs=None,
                           features: int = 1) -> str | None:
        """None when the spec (and every requested output, if any) is
        executable on inputs of ``features`` features, else a short
        reason."""
        if features > 1:
            reason = self._multivariate_reason(spec, outputs)
            if reason is not None:
                return reason
        if spec.family not in self.families:
            return f"family {spec.family!r}"
        if spec.distance not in self.distances:
            return f"distance {spec.distance!r}"
        if spec.reduction not in self.reductions:
            return "soft-min" if spec.reduction == "softmin" else \
                f"reduction {spec.reduction!r}"
        if spec.band is not None and not self.banding:
            return "banding"
        if outputs is not None:
            # normalize_outputs accepts a bare name and raises loudly
            # on unknown names — a typo must not read as "unsupported"
            req = normalize_outputs(outputs)
            missing = req - self.outputs
            if missing:
                return f"output(s) {sorted(missing)}"
            if "start" in req and spec.family not in self.window_families:
                return (f"output 'start' for family {spec.family!r} "
                        f"(window starts ride families "
                        f"{sorted(self.window_families)} here)")
            if "path" in req and spec.family != "sdtw":
                return (f"output 'path' for family {spec.family!r}: the "
                        "Hirschberg traceback recovers sdtw warping "
                        "paths only")
            if "soft_alignment" in req and spec.family != "sdtw":
                return ("output 'soft_alignment' for family "
                        f"{spec.family!r}: the soft-alignment backward "
                        "serves the sdtw recurrence only")
            argmin = req & {"start", "path"}
            if argmin and spec.soft:
                return (f"output(s) {sorted(argmin)} under soft-min: no "
                        f"argmin path on a soft-min spec (hard-min only; "
                        f"ask outputs=('soft_alignment',) for the "
                        f"smoothed alignment)")
            if "soft_alignment" in req and not spec.soft:
                return ("output 'soft_alignment' under hard-min: the "
                        "expected alignment needs a softmin spec "
                        "(reduction='softmin'; hard-min paths are "
                        "outputs=('path',))")
        return None

    def _multivariate_reason(self, spec: DPSpec, outputs) -> str | None:
        what = "multivariate (B, M, D) inputs"
        if not self.multivariate_outputs:
            return what
        if spec.family != "sdtw":
            return (f"family {spec.family!r} on {what}: the feature cost "
                    "serves the sdtw recurrence only")
        if spec.distance == "cosine":
            return f"distance 'cosine' on {what}"
        if spec.reduction not in self.multivariate_reductions:
            return f"{spec.reduction} on {what}"
        if outputs is not None:
            missing = normalize_outputs(outputs) - self.multivariate_outputs
            if missing:
                return f"output(s) {sorted(missing)} on {what}"
        return None


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything an execute() needs besides the spec: the (already
    normalized) operands, the requested sweep outputs, and per-dispatch
    options."""

    queries: Any
    reference: Any
    segment_width: int | str = 8   # "auto" = tuner-resolved at execute
    interpret: bool | None = None      # None = auto (kernels.ops)
    outputs: frozenset = _BASE_OUTPUTS
    #   sweep-level outputs the execute() must materialize — a subset of
    #   repro.core.result.SWEEP_OUTPUTS.  "start" asks for matched-
    #   window start pointers threaded through the SAME sweep (one
    #   fused pass, never a separate window pass after a cost pass);
    #   valid only on backends whose Capabilities.outputs include it.
    options: Mapping | None = None     # backend extras, e.g. {"mesh": ...}

    def option(self, key, default=None):
        return (self.options or {}).get(key, default)


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    capabilities: Capabilities
    execute: Callable[[DPSpec, ExecutionPlan], Any]   # -> SDTWResult

    def __call__(self, spec: DPSpec, plan: ExecutionPlan):
        return self.execute(spec, plan)


_REGISTRY: dict[str, Backend] = {}
_ALIASES: dict[str, tuple[str, dict]] = {}
# preference order for select(): fastest general-purpose engine first
_PRIORITY = ("engine", "kernel", "ref", "quantized", "distributed")


def _device_default() -> str:
    """The platform auto-selection keys off (overridable in tests)."""
    import jax
    return jax.default_backend()


def _priority() -> tuple:
    """Preference order for auto-selection, device-aware: on TPU the
    Pallas wavefront kernel outruns the XLA engine for every spec it
    supports (hard- and soft-min since the carry-channel executor), so
    it is tried first there; everywhere else the kernel would run
    interpreted and the engine stays the default."""
    if _device_default() == "tpu":
        return ("kernel",) + tuple(n for n in _PRIORITY if n != "kernel")
    return _PRIORITY


def register(backend: Backend, *, overwrite: bool = False) -> Backend:
    if not overwrite and backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def register_alias(alias: str, target: str, **spec_overrides) -> None:
    """An alias resolves to ``target`` with fields of the caller's spec
    force-overridden (e.g. ``soft`` -> engine with reduction=softmin)."""
    _ALIASES[alias] = (target, spec_overrides)


def _ensure_builtins() -> None:
    if "engine" not in _REGISTRY:
        from repro.backends import builtin  # noqa: F401  (self-registers)


def names(*, aliases: bool = True) -> list[str]:
    _ensure_builtins()
    out = sorted(_REGISTRY)
    if aliases:
        out += sorted(_ALIASES)
    return out


def _expand(name: str, spec: DPSpec) -> tuple[Backend, DPSpec]:
    """Alias expansion: map an alias to its target backend AND apply its
    spec overrides. Every capability query goes through here so an alias
    is never validated (or executed) against the un-rewritten spec."""
    _ensure_builtins()
    if name in _ALIASES:
        target, overrides = _ALIASES[name]
        spec = dataclasses.replace(spec, **overrides)
        name = target
    try:
        return _REGISTRY[name], spec
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{names()}") from None


def get(name: str) -> Backend:
    """Look up a backend (aliases map to their target). NOTE: alias spec
    overrides are NOT applied here — use :func:`resolve` (or
    :func:`select`) whenever you intend to execute, so the rewritten
    spec travels with the backend."""
    return _expand(name, DPSpec())[0]


def supports(name: str, spec: DPSpec, *, outputs=None,
             features: int = 1) -> bool:
    backend, spec = _expand(name, spec)
    return backend.capabilities.unsupported_reason(
        spec, outputs=outputs, features=features) is None


def capable(spec: DPSpec, *, exact_only: bool = False,
            outputs=None,
            differentiable: bool = False,
            features: int = 1) -> list[str]:
    """Backend names able to execute ``spec`` (and fulfill every
    requested output, when asked), in preference order (device-aware:
    the kernel leads on TPU, the engine elsewhere).

    ``differentiable=True`` keeps only backends declaring NaN-free
    gradients.  The Pallas kernel qualifies for soft-min specs: its
    costs carry the fused reverse-sweep custom_vjp
    (repro.kernels.backward), so jax.grad works at kernel speed.
    ``features`` > 1 asks for multivariate (B, M, D) inputs; on them a
    backend is differentiable only where its soft-min needs no backward
    of its own (the kernel's fused backward is univariate, and the
    kernel declines soft-min there).
    """
    _ensure_builtins()
    ordered = [n for n in _priority() if n in _REGISTRY]
    ordered += [n for n in sorted(_REGISTRY) if n not in ordered]
    out = []
    for n in ordered:
        caps = _REGISTRY[n].capabilities
        if caps.unsupported_reason(spec, outputs=outputs,
                                   features=features) is None \
                and (caps.exact or not exact_only) \
                and (caps.differentiable or not differentiable):
            out.append(n)
    return out


def validate(name: str, spec: DPSpec) -> Backend:
    """Return the backend or raise a capability error naming who can.
    Alias spec overrides are applied before validation (use
    :func:`resolve` when you also need the rewritten spec)."""
    return resolve(name, spec)[0]


def resolve(name: str, spec: DPSpec, *, outputs=None,
            features: int = 1) -> tuple[Backend, DPSpec]:
    """Alias expansion + capability validation.

    Returns the concrete backend and the (possibly alias-rewritten)
    spec — e.g. ``resolve("soft", spec)`` -> (engine, spec with
    reduction="softmin").  ``outputs`` additionally requires the
    backend to fulfill every requested result field (e.g.
    ``{"start"}`` for matched windows), failing with the same loud
    who-can-instead error.  ``features`` > 1 validates multivariate
    (B, M, D) inputs of that many features.
    """
    backend, spec = _expand(name, spec)
    reason = backend.capabilities.unsupported_reason(
        spec, outputs=outputs, features=features)
    if reason is not None:
        alternatives = [n for n in capable(spec, outputs=outputs,
                                           features=features)
                        if n != backend.name]
        hint = f": use one of {alternatives}" if alternatives else ""
        raise ValueError(
            f"backend {backend.name!r} does not support {reason} "
            f"(spec {spec.describe()}){hint}")
    return backend, spec


def select(spec: DPSpec, *, preferred: str | None = None,
           outputs=None,
           differentiable: bool = False,
           workload: tuple | None = None,
           features: int = 1) -> tuple[Backend, DPSpec]:
    """Pick a backend for the spec: the preferred one when capable,
    else the first capable backend in preference order (the auto-
    fallback path: ``preferred=None, outputs={"start", ...}`` lands on
    the fastest window-capable backend).  ``differentiable=True``
    restricts auto-selection to gradient-safe backends (see
    :func:`capable`) — a named ``preferred`` backend is taken at the
    caller's word.

    ``workload=(m, n, batch)`` lets auto-selection consult the
    ``repro.tune`` cache: when this exact workload has a measured
    verdict on this machine, the measured winner beats the static
    device-priority guess (still restricted to capable backends — a
    verdict can re-rank choices, never bypass capability checks).
    Tuning verdicts are univariate: ``features`` > 1 (multivariate
    (B, M, D) inputs) selects by capability and priority alone.

    Returns ``(backend, spec)`` with alias overrides applied — execute
    with the RETURNED spec, never the one you passed in.
    """
    if preferred is not None:
        backend, spec = resolve(preferred, spec, outputs=outputs,
                                features=features)
        _record_selection(backend.name, spec, "preferred by caller")
        return backend, spec
    choices = capable(spec, outputs=outputs,
                      differentiable=differentiable, features=features)
    if workload is not None and choices and features == 1:
        tuned = _tuned_choice(spec, workload, outputs, choices)
        if tuned is not None:
            _record_selection(tuned, spec, "tuned verdict")
            return _REGISTRY[tuned], spec
    if not choices:
        what = f"spec {spec.describe()}"
        if features > 1:
            what += f" on multivariate inputs of {features} features"
        if outputs is not None:
            what += f" with outputs={sorted(normalize_outputs(outputs))}"
        if differentiable:
            what += " differentiably"
        # name WHY the most-capable backend declines, so spec-level
        # impossibilities (e.g. start under soft-min) explain themselves
        reason = _REGISTRY["engine"].capabilities.unsupported_reason(
            spec, outputs=outputs) if "engine" in _REGISTRY else None
        hint = f" (engine: {reason})" if reason else ""
        raise ValueError(f"no registered backend supports {what}{hint}")
    why = (f"first capable of {len(choices)} on device="
           f"{_device_default()}")
    if differentiable:
        why += ", differentiable"
    _record_selection(choices[0], spec, why)
    return _REGISTRY[choices[0]], spec


def _tuned_choice(spec: DPSpec, workload: tuple, outputs,
                  choices: list[str]) -> str | None:
    """The tuning cache's pick for (m, n, batch), when it has one and
    the pick is among the capable choices.  Best-effort by design —
    any tuning-layer problem silently falls back to static priority,
    because selection must keep working on machines that never tuned."""
    try:
        from repro.tune import cached_verdict
        m, n, batch = workload
        verdict = cached_verdict(spec, m=m, n=n, batch=batch,
                                 outputs=outputs)
        if verdict is not None and verdict.get("backend") in choices:
            return verdict["backend"]
    except Exception:
        pass
    return None


def _record_selection(name: str, spec: DPSpec, why: str) -> None:
    """Selection observability: which backend won and why — counters in
    the default registry (``registry.select.<backend>``) plus a debug
    log line, so auto-selection drift (e.g. the TPU kernel-first rule)
    shows up in exported metrics, not just in someone's recollection."""
    m = obs.default_registry()
    m.inc("registry.select.calls")
    m.inc(f"registry.select.{name}")
    log.debug("select -> %s (%s) for spec %s", name, why, spec.describe())


def capability_rows() -> list[dict]:
    """One dict per backend — the README/benchmark capability table."""
    _ensure_builtins()
    rows = []
    for name in sorted(_REGISTRY):
        c = _REGISTRY[name].capabilities
        rows.append({
            "backend": name,
            "families": ",".join(sorted(c.families)),
            "distances": ",".join(sorted(c.distances)),
            "reductions": ",".join(sorted(c.reductions)),
            "banding": c.banding,
            "differentiable": c.differentiable,
            "per_query_reference": c.per_query_reference,
            "exact": c.exact,
            "outputs": ",".join(sorted(c.outputs - _BASE_OUTPUTS)) or "-",
            "multivariate": ",".join(sorted(c.multivariate_outputs)) or "-",
            "device": c.device,
        })
    return rows
