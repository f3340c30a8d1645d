"""Declarative SLOs over the metrics registry: objectives parsed from a tiny
expression grammar, judged live by a sampler thread with SRE-style fast/slow
multi-window burn rates, or point-in-time against a registry rebuilt from
recorded artifacts (`data check_slo`).

Objective grammar (one expression string per objective):

    <histogram> p<NN> <op> <threshold>      serve_ttft_seconds p99 < 0.5
    <counter> / <counter> <op> <threshold>  serve_request_errors_total / serve_requests_total <= 0.01
    <gauge|counter> <op> <threshold>        training_goodput_ratio >= 0.85

with ``<op>`` one of ``<  <=  >  >=``. Any metric reference may carry a
Prometheus-style label selector — ``serve_tenant_shed_total{tenant="bulk"} /
serve_tenant_requests_total{tenant="bulk"} <= 0.05`` — judging exactly that
series instead of the unlabeled one (per-tenant SLOs ride this). A metric
absent from the registry (or a histogram/denominator with no observations
yet) makes the objective *unjudgeable* — skipped, never breaching: booting
quiet is not an outage.

Live judging: each sampler tick evaluates every objective and feeds the
verdict into a :class:`BurnRateEvaluator` — breach when the fast window's
burn rate trips (quick detection), recovery only once the slow window drains
too (hysteresis), error budget read over the slow window. Transitions emit
``slo/breach`` / ``slo/recovered`` events; ``slo_status{objective}`` and
``slo_error_budget_remaining{objective}`` gauges live on the same registry
the objectives read, so they ride the existing /metrics surface.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable, Mapping, Optional, Sequence, Union

from modalities_tpu.resilience.events import record_event
from modalities_tpu.telemetry.metrics import Counter, Gauge, Histogram, MetricsRegistry

logger = logging.getLogger(__name__)

_OPS: dict[str, Callable[[float, float], bool]] = {
    "<": lambda v, t: v < t,
    "<=": lambda v, t: v <= t,
    ">": lambda v, t: v > t,
    ">=": lambda v, t: v >= t,
}

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_NUM = r"[-+]?\d+(?:\.\d+)?(?:[eE][-+]?\d+)?"
_SEL = r"(?:\{([^{}]*)\})?"  # optional {label="value", ...} series selector
_QUANTILE_RE = re.compile(rf"^({_NAME}){_SEL}\s+p(\d+(?:\.\d+)?)\s*(<=|>=|<|>)\s*({_NUM})$")
_RATIO_RE = re.compile(rf"^({_NAME}){_SEL}\s*/\s*({_NAME}){_SEL}\s*(<=|>=|<|>)\s*({_NUM})$")
_VALUE_RE = re.compile(rf"^({_NAME}){_SEL}\s*(<=|>=|<|>)\s*({_NUM})$")
_LABEL_PAIR_RE = re.compile(r'^([a-zA-Z_][a-zA-Z0-9_]*)\s*=\s*"([^"]*)"$')


def _parse_selector(inner: Optional[str]) -> dict:
    """``tenant="bulk", reason="brownout"`` → label kwargs dict (the braces
    are stripped by the grammar regex; None/empty = no selector)."""
    if not inner or not inner.strip():
        return {}
    labels: dict[str, str] = {}
    for part in inner.split(","):
        m = _LABEL_PAIR_RE.match(part.strip())
        if m is None:
            raise ValueError(
                f'bad label selector fragment {part.strip()!r} — expected label="value"'
            )
        labels[m.group(1)] = m.group(2)
    return labels


@dataclass
class Objective:
    """One parsed SLO objective plus its burn-rate tuning."""

    name: str
    expr: str
    kind: str  # "quantile" | "ratio" | "value"
    metric: str
    op: str
    threshold: float
    quantile: Optional[float] = None  # kind == "quantile"
    denominator: Optional[str] = None  # kind == "ratio"
    labels: dict = field(default_factory=dict)  # series selector on `metric`
    den_labels: dict = field(default_factory=dict)  # selector on `denominator`
    budget: float = 0.01  # allowed bad-sample fraction
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    fast_burn: float = 14.0
    slow_burn: float = 2.0


def parse_objective(name: str, expr: str, **opts) -> Objective:
    """Parse one expression string into an :class:`Objective`; ``opts`` are
    burn-rate overrides (budget, fast/slow window seconds, burn thresholds)."""
    text = " ".join(str(expr).split())
    m = _QUANTILE_RE.match(text)
    if m:
        metric, sel, q, op, thr = m.groups()
        if not 0.0 < float(q) < 100.0:
            raise ValueError(f"objective {name!r}: quantile p{q} outside (0, 100)")
        return Objective(
            name=name, expr=text, kind="quantile", metric=metric, op=op,
            threshold=float(thr), quantile=float(q) / 100.0,
            labels=_parse_selector(sel), **opts,
        )
    m = _RATIO_RE.match(text)
    if m:
        num, num_sel, den, den_sel, op, thr = m.groups()
        return Objective(
            name=name, expr=text, kind="ratio", metric=num, op=op,
            threshold=float(thr), denominator=den,
            labels=_parse_selector(num_sel), den_labels=_parse_selector(den_sel),
            **opts,
        )
    m = _VALUE_RE.match(text)
    if m:
        metric, sel, op, thr = m.groups()
        return Objective(
            name=name, expr=text, kind="value", metric=metric, op=op,
            threshold=float(thr), labels=_parse_selector(sel), **opts,
        )
    raise ValueError(
        f"objective {name!r}: cannot parse {expr!r} — expected "
        "'<metric> pNN <op> <num>', '<metric> / <metric> <op> <num>', "
        "or '<metric> <op> <num>'"
    )


def _metric_value(objective: Objective, registry: MetricsRegistry) -> Optional[float]:
    """Current value of the objective's expression, or None when unjudgeable."""
    metric = registry.get(objective.metric)
    if metric is None:
        return None
    if objective.kind == "quantile":
        if not isinstance(metric, Histogram) or metric.count(**objective.labels) <= 0:
            return None
        return metric.quantile(objective.quantile, **objective.labels)
    if objective.kind == "ratio":
        den = registry.get(objective.denominator)
        if den is None:
            return None
        den_value = den.value(**objective.den_labels)
        if den_value <= 0:
            return None
        return metric.value(**objective.labels) / den_value
    if not isinstance(metric, (Counter, Gauge)):
        return None
    if isinstance(metric, Gauge) and not objective.labels:
        series = metric.series_snapshot()
        if series and () not in series:
            # labeled-only gauge (per-device headroom, per-executable memscope
            # peak): judge the WORST series for the op's direction — max for a
            # ceiling objective, min for a floor — so one bad device/executable
            # cannot hide behind a healthy sibling.
            worst = max if objective.op in ("<", "<=") else min
            return worst(series.values())
    return metric.value(**objective.labels)


def evaluate_objective(
    objective: Objective, registry: MetricsRegistry
) -> tuple[Optional[bool], Optional[float]]:
    """(ok, observed) for one objective against a live registry; ok is None
    when the expression is unjudgeable right now (metric absent / no data)."""
    value = _metric_value(objective, registry)
    if value is None:
        return None, None
    return _OPS[objective.op](value, objective.threshold), value


class BurnRateEvaluator:
    """Multi-window burn-rate state machine for ONE objective.

    Every sample is good or bad; burn rate over a window is
    ``bad_fraction / budget`` (burn 1.0 = spending budget exactly at the
    sustainable rate). Breach trips when the fast OR slow window exceeds its
    burn threshold; recovery requires BOTH windows clear, so a breach holds
    until the slow window drains (hysteresis against flapping). The error
    budget gauge is ``1 − slow_burn_rate`` clamped to [0, 1]: it exhausts at
    sustained slow-window burn ≥ 1 and refills as bad samples age out."""

    def __init__(self, objective: Objective, time_fn: Callable[[], float] = time.monotonic):
        self.objective = objective
        self._time_fn = time_fn
        self._samples: deque[tuple[float, bool]] = deque()  # (ts, bad)
        self.breaching = False
        self.last_value: Optional[float] = None
        self.fast_burn_rate = 0.0
        self.slow_burn_rate = 0.0

    def _window_bad_fraction(self, now: float, window_s: float) -> float:
        total = bad = 0
        for ts, is_bad in self._samples:
            if now - ts <= window_s:
                total += 1
                bad += is_bad
        return bad / total if total else 0.0

    def observe(self, ok: Optional[bool], value: Optional[float] = None) -> Optional[str]:
        """Feed one sample (None = unjudgeable, keeps state but adds no
        sample). Returns "breach" / "recovered" on a transition, else None."""
        now = self._time_fn()
        if ok is not None:
            self._samples.append((now, not ok))
            self.last_value = value
        horizon = max(self.objective.fast_window_s, self.objective.slow_window_s)
        while self._samples and now - self._samples[0][0] > horizon:
            self._samples.popleft()

        budget = max(self.objective.budget, 1e-9)
        fast = self._window_bad_fraction(now, self.objective.fast_window_s) / budget
        slow = self._window_bad_fraction(now, self.objective.slow_window_s) / budget
        self.fast_burn_rate, self.slow_burn_rate = fast, slow

        burning = fast >= self.objective.fast_burn or slow >= self.objective.slow_burn
        if burning and not self.breaching:
            self.breaching = True
            return "breach"
        if not burning and self.breaching:
            self.breaching = False
            return "recovered"
        return None

    def budget_remaining(self) -> float:
        return min(max(1.0 - self.slow_burn_rate, 0.0), 1.0)


class SLOEngine:
    """Judges a list of objectives against one registry.

    ``sample_once()`` is the whole evaluation step (tests and the fleet
    probation loop call it directly); ``start()`` runs it on a daemon sampler
    thread every ``sample_interval_s``. Status gauges and breach counters are
    registered on the SAME registry the objectives read."""

    def __init__(
        self,
        objectives: Sequence[Objective],
        registry: MetricsRegistry,
        sample_interval_s: Optional[float] = None,
        scope: str = "",
        time_fn: Callable[[], float] = time.monotonic,
    ):
        if sample_interval_s is None:
            sample_interval_s = float(os.environ.get("MODALITIES_TPU_SLO_SAMPLE_S", "5.0"))
        self.objectives = list(objectives)
        self.registry = registry
        self.sample_interval_s = sample_interval_s
        self.scope = scope
        self._evaluators = {
            o.name: BurnRateEvaluator(o, time_fn=time_fn) for o in self.objectives
        }
        self._m_status = registry.gauge(
            "slo_status", "1 = objective within SLO, 0 = breaching"
        )
        self._m_budget = registry.gauge(
            "slo_error_budget_remaining", "fraction of slow-window error budget left"
        )
        self._m_breaches = registry.counter(
            "slo_breaches_total", "breach transitions per objective"
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- evaluation
    def sample_once(self) -> dict[str, Optional[bool]]:
        """Evaluate every objective once; update burn state, gauges, events."""
        verdicts: dict[str, Optional[bool]] = {}
        for objective in self.objectives:
            ok, value = evaluate_objective(objective, self.registry)
            verdicts[objective.name] = ok
            evaluator = self._evaluators[objective.name]
            transition = evaluator.observe(ok, value)
            self._m_status.set(0.0 if evaluator.breaching else 1.0, objective=objective.name)
            self._m_budget.set(evaluator.budget_remaining(), objective=objective.name)
            if transition == "breach":
                self._m_breaches.inc(objective=objective.name)
                record_event(
                    "slo/breach",
                    objective=objective.name,
                    expr=objective.expr,
                    value=value,
                    fast_burn_rate=evaluator.fast_burn_rate,
                    slow_burn_rate=evaluator.slow_burn_rate,
                    scope=self.scope,
                )
                logger.warning(
                    "SLO breach%s: %s (%s, value=%s)",
                    f" [{self.scope}]" if self.scope else "",
                    objective.name, objective.expr, value,
                )
            elif transition == "recovered":
                record_event(
                    "slo/recovered",
                    objective=objective.name,
                    expr=objective.expr,
                    value=value,
                    scope=self.scope,
                )
                logger.info(
                    "SLO recovered%s: %s",
                    f" [{self.scope}]" if self.scope else "", objective.name,
                )
        return verdicts

    def breaching(self) -> list[str]:
        """Names of objectives currently in breach (the rollout verdict)."""
        return [name for name, ev in self._evaluators.items() if ev.breaching]

    def status(self) -> dict[str, dict]:
        return {
            name: {
                "breaching": ev.breaching,
                "budget_remaining": ev.budget_remaining(),
                "last_value": ev.last_value,
            }
            for name, ev in self._evaluators.items()
        }

    # ---------------------------------------------------------------- thread
    def _run(self) -> None:
        while not self._stop.wait(self.sample_interval_s):
            try:
                self.sample_once()
            except Exception:  # judging must never take the server down
                logger.exception("SLO sampler tick failed")

    def start(self) -> "SLOEngine":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name=f"slo-sampler{('-' + self.scope) if self.scope else ''}",
                daemon=True,
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# --------------------------------------------------------------------- spec
def load_slo_spec(source: Union[str, Path, Mapping]) -> tuple[list[Objective], dict]:
    """Load objectives from a config mapping (the ``slo:`` block) or a YAML
    file path. Returns (objectives, engine options) where options currently
    carries ``sample_interval_s`` when the spec sets it."""
    if isinstance(source, (str, Path)):
        import yaml

        with open(source) as f:
            spec = yaml.safe_load(f) or {}
    else:
        spec = dict(source)
    if "objectives" not in spec:
        raise ValueError("SLO spec needs an 'objectives' list")
    tuning_keys = ("budget", "fast_window_s", "slow_window_s", "fast_burn", "slow_burn")
    objectives = []
    for row in spec["objectives"] or []:
        row = dict(row)
        name, expr = row.pop("name"), row.pop("expr")
        opts = {k: float(row.pop(k)) for k in tuning_keys if k in row}
        if row:
            raise ValueError(f"objective {name!r}: unknown keys {sorted(row)}")
        objectives.append(parse_objective(name, expr, **opts))
    options = {}
    if spec.get("sample_interval_s") is not None:
        options["sample_interval_s"] = float(spec["sample_interval_s"])
    return objectives, options


def tenant_objectives(
    tenant_names: Iterable[str], threshold: float = 0.05
) -> list[Objective]:
    """Auto-generated per-tenant SLO objectives (one per declared tenant): the
    fraction of a tenant's arrivals that were shed stays under `threshold`.
    Named ``tenant_<name>_error_rate`` — the serving engine reads each one's
    ``budget_remaining`` to drive burn-aware victim selection, so a tenant the
    system has already been shedding from is protected next time."""
    return [
        parse_objective(
            f"tenant_{name}_error_rate",
            f'serve_tenant_shed_total{{tenant="{name}"}} / '
            f'serve_tenant_requests_total{{tenant="{name}"}} <= {threshold}',
        )
        for name in tenant_names
    ]


# ------------------------------------------------- recorded-run evaluation
def _iter_jsonl(path: Path) -> Iterable[dict]:
    import json

    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:  # torn tail line from a killed run
                continue
            if isinstance(row, dict):
                yield row


def replay_sink_into_registry(sink_path: Union[str, Path], registry: MetricsRegistry) -> int:
    """Rebuild judgeable series from a telemetry sink (file or folder of
    ``telemetry_rank_*.jsonl``): serve_request records re-observe the serving
    histograms/counters, goodput spans set ``training_goodput_ratio``, and
    ``mfu_waterfall`` events set ``training_mfu_achieved``. Returns the
    number of records replayed."""
    sink_path = Path(sink_path)
    files = (
        sorted(sink_path.glob("telemetry_rank_*.jsonl"))
        if sink_path.is_dir()
        else [sink_path]
    )
    files = [p for p in files if p.exists()]
    h_ttft = registry.histogram("serve_ttft_seconds", "time to first token")
    h_latency = registry.histogram("serve_request_latency_seconds", "request latency")
    c_requests = registry.counter("serve_requests_total", "finished requests")
    c_errors = registry.counter("serve_request_errors_total", "failed requests")
    replayed = 0
    max_in_use: Optional[float] = None
    min_headroom: dict[str, float] = {}
    for path in files:
        for row in _iter_jsonl(path):
            event = row.get("event")
            if event == "serve_request":
                replayed += 1
                c_requests.inc()
                if row.get("finish_reason") == "error":
                    c_errors.inc()
                if row.get("ttft_s") is not None:
                    h_ttft.observe(float(row["ttft_s"]))
                if row.get("latency_s") is not None:
                    h_latency.observe(float(row["latency_s"]))
            elif event == "mfu_waterfall":
                replayed += 1
                if row.get("achieved") is not None:
                    registry.gauge("training_mfu_achieved", "").set(float(row["achieved"]))
            elif event == "memscope_timeline":
                replayed += 1
                if row.get("bytes_in_use") is not None:
                    # fold to the run's MAX in-use: the worst moment is the one
                    # a ceiling objective should judge
                    max_in_use = max(float(row["bytes_in_use"]), max_in_use or 0.0)
                for device, headroom in (row.get("headroom_bytes") or {}).items():
                    # MIN per device: a headroom FLOOR objective must see the
                    # tightest sample, not the last one
                    prior = min_headroom.get(device)
                    value = float(headroom)
                    min_headroom[device] = value if prior is None else min(value, prior)
    if max_in_use is not None:
        registry.gauge("training_hbm_bytes_in_use", "").set(max_in_use)
    if min_headroom:
        headroom_gauge = registry.gauge("memscope_device_headroom_bytes", "")
        for device, headroom in min_headroom.items():
            headroom_gauge.set(headroom, device=device)
    try:
        from modalities_tpu.telemetry.goodput import summarize_sink

        summary = summarize_sink(sink_path)
        pct = (summary.get("combined") or {}).get("goodput_pct")
        if pct is not None:
            registry.gauge("training_goodput_ratio", "").set(float(pct) / 100.0)
            replayed += 1
    except Exception:  # sink without span records — serving-only is fine
        pass
    return replayed


def replay_memscope_into_registry(
    report_path: Union[str, Path], registry: MetricsRegistry
) -> int:
    """Fold a ``memscope.json`` static report into
    ``memscope_bucket_bytes{executable,bucket}`` gauges so bucket-level memory
    objectives are judgeable offline — accepts both the multi-executable shape
    (``{"executables": {...}}``) and a single bare report."""
    import json

    data = json.loads(Path(report_path).read_text())
    executables = data.get("executables") or {"executable": data}
    bucket_gauge = registry.gauge("memscope_bucket_bytes", "")
    lifted = 0
    for executable, report in executables.items():
        for bucket, nbytes in (report.get("buckets") or {}).items():
            bucket_gauge.set(float(nbytes), executable=executable, bucket=bucket)
            lifted += 1
        total = (report.get("memory_analysis") or {}).get("total_bytes")
        if total is not None:
            registry.gauge("memscope_predicted_peak_bytes", "").set(
                float(total), executable=executable
            )
            lifted += 1
    return lifted


def evaluate_recorded(
    objectives: Sequence[Objective], registry: MetricsRegistry
) -> dict:
    """Point-in-time verdict (no burn windows — the recording already
    happened) over a replayed registry: ok / breaching / skipped lists plus
    per-objective observed values."""
    report = {"ok": [], "breaching": [], "skipped": [], "values": {}}
    for objective in objectives:
        ok, value = evaluate_objective(objective, registry)
        report["values"][objective.name] = value
        if ok is None:
            report["skipped"].append(objective.name)
        elif ok:
            report["ok"].append(objective.name)
        else:
            report["breaching"].append(objective.name)
    return report
