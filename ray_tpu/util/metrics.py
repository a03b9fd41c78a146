"""Application metrics API: Counter / Gauge / Histogram.

Equivalent of the reference's ray.util.metrics
(reference: python/ray/util/metrics.py Counter/Gauge/Histogram over the C++
OpenCensus pipeline src/ray/stats/metric.h:103-160 exported to Prometheus).
Here metrics register into prometheus_client (in-process registry); expose
them with `start_metrics_server(port)` and scrape, or read programmatically
via `collect()`.

Fleet plane (docs/OBSERVABILITY.md "Fleet metrics & goodput"): each process
stays the owner of its own registry; ``collect_families()`` snapshots it
WITH metric kinds preserved, and ``FleetAggregator`` (driven by the Serve
controller) merges many such snapshots into one scrapeable plane — entity
labels per source, per-kind rollups (sum counters, last-write gauges,
bucket-wise histogram merge), and a bounded ring-buffer time-series history
that outlives the processes it sampled.
"""
from __future__ import annotations

import logging
import threading
from bisect import bisect_left
from collections import Counter as _Tally, deque
from typing import Sequence

logger = logging.getLogger("ray_tpu.metrics")

try:
    import prometheus_client as _prom
    from prometheus_client import CollectorRegistry

    _AVAILABLE = True
except ImportError:  # pragma: no cover - baked into this image
    _AVAILABLE = False

_registry = None
_registry_lock = threading.Lock()


def _get_registry():
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = CollectorRegistry()
        return _registry


class _Metric:
    def __init__(self, name: str, description: str, tag_keys: Sequence[str]):
        if not _AVAILABLE:
            raise RuntimeError("prometheus_client not available")
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys)
        self._default_tags: dict[str, str] = {}

    def set_default_tags(self, tags: dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _labels(self, tags: dict[str, str] | None):
        merged = {**self._default_tags, **(tags or {})}
        missing = set(self.tag_keys) - set(merged)
        if missing:
            raise ValueError(f"metric {self.name} missing tags: {sorted(missing)}")
        return [merged[k] for k in self.tag_keys]


class Counter(_Metric):
    def __init__(self, name, description="", tag_keys=()):
        super().__init__(name, description, tag_keys)
        self._c = _prom.Counter(
            name, description, labelnames=self.tag_keys, registry=_get_registry()
        )

    def inc(self, value: float = 1.0, tags: dict | None = None):
        c = self._c.labels(*self._labels(tags)) if self.tag_keys else self._c
        c.inc(value)


class Gauge(_Metric):
    def __init__(self, name, description="", tag_keys=()):
        super().__init__(name, description, tag_keys)
        self._g = _prom.Gauge(
            name, description, labelnames=self.tag_keys, registry=_get_registry()
        )

    def set(self, value: float, tags: dict | None = None):
        g = self._g.labels(*self._labels(tags)) if self.tag_keys else self._g
        g.set(value)


class Histogram(_Metric):
    def __init__(self, name, description="", boundaries=(), tag_keys=()):
        super().__init__(name, description, tag_keys)
        self.boundaries = tuple(boundaries)
        kwargs = {"registry": _get_registry(), "labelnames": self.tag_keys}
        if boundaries:
            kwargs["buckets"] = self.boundaries
        self._h = _prom.Histogram(name, description, **kwargs)

    def observe(self, value: float, tags: dict | None = None):
        h = self._h.labels(*self._labels(tags)) if self.tag_keys else self._h
        h.observe(value)

    def observe_many(self, values: Sequence[float],
                     tags: dict | None = None):
        """``observe`` every one of ``values`` (a step's rows): each lands
        in the bucket and the sum ``observe`` would put it in, but the sum
        and a bucket are touched once for all of them."""
        if not values:
            return
        h = self._h.labels(*self._labels(tags)) if self.tag_keys else self._h
        bounds = h._upper_bounds
        h._sum.inc(sum(values))
        # a value's bucket: the first bound it does not pass (the last is
        # +Inf)
        for i, n in _Tally(bisect_left(bounds, v) for v in values).items():
            h._buckets[i].inc(n)


# Idempotent named-metric factories: prometheus_client raises on duplicate
# registration, but library-internal metrics (e.g. the serve/llm engine,
# which may be constructed several times in one process) want one shared
# instrument per name. Keyed on name; kind mismatches fail loudly.
_named: dict[str, _Metric] = {}
_named_lock = threading.Lock()
# names already warned about description drift — warn ONCE per name, not
# once per get (engine construction re-gets every metric)
_desc_warned: set[str] = set()


def _get_named(cls, name: str, description: str, tag_keys, **kwargs):
    with _named_lock:
        m = _named.get(name)
        if m is None:
            m = cls(name, description, tag_keys=tag_keys, **kwargs)
            _named[name] = m
        elif type(m) is not cls:
            raise TypeError(
                f"metric {name!r} already registered as {type(m).__name__}, "
                f"requested {cls.__name__}"
            )
        else:
            # same fail-loudly contract as the kind check: handing back an
            # instrument whose schema differs from what the caller asked
            # for would silently mislabel (tag_keys) or misbucket
            # (boundaries) every later observation
            if tuple(tag_keys) != m.tag_keys:
                raise ValueError(
                    f"metric {name!r} already registered with tag_keys="
                    f"{m.tag_keys}, requested {tuple(tag_keys)}"
                )
            if isinstance(m, Histogram):
                want = tuple(kwargs.get("boundaries") or ())
                if want != m.boundaries:
                    raise ValueError(
                        f"histogram {name!r} already registered with "
                        f"boundaries={m.boundaries}, requested {want}"
                    )
            # description drift is not schema-breaking (the first HELP
            # string keeps being exported) but it means code and docs
            # disagree about what the metric measures — warn once.
            # Omitted descriptions (lookup-style ``counter(name)``) are
            # not drift.
            if (
                description
                and m.description
                and description != m.description
                and name not in _desc_warned
            ):
                _desc_warned.add(name)
                logger.warning(
                    "metric %r re-registered with a different description "
                    "(%r vs original %r); keeping the original — update "
                    "the caller or the docs",
                    name, description, m.description,
                )
        return m


def counter(name: str, description: str = "", tag_keys=()) -> Counter:
    """Get-or-create a process-wide Counter by name."""
    return _get_named(Counter, name, description, tag_keys)


def gauge(name: str, description: str = "", tag_keys=()) -> Gauge:
    """Get-or-create a process-wide Gauge by name."""
    return _get_named(Gauge, name, description, tag_keys)


def histogram(
    name: str, description: str = "", boundaries=(), tag_keys=()
) -> Histogram:
    """Get-or-create a process-wide Histogram by name."""
    return _get_named(
        Histogram, name, description, tag_keys, boundaries=boundaries
    )


def start_metrics_server(port: int = 9090, addr: str = "0.0.0.0"):
    """Expose the registry on http://addr:port/metrics (Prometheus scrape
    target — the analog of the reference's per-node metrics agent).

    Returns ``(server, port)``: the bound WSGI server (call
    ``server.shutdown()`` to stop it) and the ACTUAL bound port, so
    ``port=0`` binds an ephemeral port — multi-process nodes and tests
    can scrape without port collisions."""
    from wsgiref.simple_server import WSGIRequestHandler, make_server

    try:  # threaded scrape handling when the installed client has it
        from prometheus_client.exposition import ThreadingWSGIServer as _Srv
    except ImportError:  # pragma: no cover - baked into this image
        from wsgiref.simple_server import WSGIServer as _Srv

    class _SilentHandler(WSGIRequestHandler):
        def log_message(self, format, *args):
            """Scrapes land every few seconds — keep them off stderr."""

    server = make_server(
        addr, int(port), _prom.make_wsgi_app(registry=_get_registry()),
        server_class=_Srv, handler_class=_SilentHandler,
    )
    thread = threading.Thread(
        target=server.serve_forever, daemon=True, name="metrics-server"
    )
    thread.start()
    return server, server.server_port


def collect(prefix: str | None = None) -> dict[str, float]:
    """Programmatic snapshot: {'name{label=v}': value} for tests/inspection.

    ``prefix`` filters by sample-name prefix (e.g. ``"llm_prefix"``) so
    benchmarks and dashboards can pull one subsystem's metrics without
    walking the whole registry."""
    out = {}
    for family in _get_registry().collect():
        for sample in family.samples:
            if prefix is not None and not sample.name.startswith(prefix):
                continue
            labels = ",".join(f"{k}={v}" for k, v in sorted(sample.labels.items()))
            key = f"{sample.name}{{{labels}}}" if labels else sample.name
            out[key] = sample.value
    return out


# ---------------------------------------------------------------------------
# Fleet metrics plane (docs/OBSERVABILITY.md "Fleet metrics & goodput")
# ---------------------------------------------------------------------------


def collect_families(prefix: str | None = None) -> dict[str, dict]:
    """Structured registry snapshot preserving metric KIND — the fleet
    merge needs per-kind semantics (sum counters, last-write gauges,
    bucket-wise histogram merge) that the flat ``collect()`` mapping
    cannot express.

    -> ``{family_name: {"type", "help", "samples": [{"name", "labels",
    "value"}, ...]}}``. Sample names keep the Prometheus suffix contracts
    (``_total`` for counters; ``_bucket``/``_sum``/``_count`` for
    histograms, with the bucket bound as a ``le`` label); ``_created``
    bookkeeping samples are dropped (timestamps, not mergeable). The
    result is plain JSON-safe dicts, so it crosses actor RPCs as-is —
    this is the payload ``metrics_report()`` control methods return."""
    out: dict[str, dict] = {}
    for family in _get_registry().collect():
        if prefix is not None and not family.name.startswith(prefix):
            continue
        samples = [
            {
                "name": s.name,
                "labels": dict(s.labels),
                "value": float(s.value),
            }
            for s in family.samples
            if not s.name.endswith("_created")
        ]
        out[family.name] = {
            "type": family.type,
            "help": family.documentation,
            "samples": samples,
        }
    return out


def sample_key(name: str, labels: dict[str, str]) -> str:
    """Canonical series key, same format as ``collect()`` keys:
    ``name{k=v,...}`` with labels sorted — history rings and tests agree
    on one spelling."""
    pairs = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{pairs}}}" if pairs else name


def _escape_label(value: str) -> str:
    return (
        str(value)
        .replace("\\", r"\\")
        .replace("\n", r"\n")
        .replace('"', r"\"")
    )


def _format_value(value: float) -> str:
    f = float(value)
    if f == float("inf"):
        return "+Inf"
    if f == float("-inf"):
        return "-Inf"
    if f.is_integer():
        return str(int(f))
    return repr(f)


def render_prometheus(families: dict[str, dict]) -> str:
    """Prometheus text exposition (format 0.0.4) of a
    ``collect_families()``-shaped dict — the body served at the
    dashboard's ``/metrics/fleet`` scrape target."""
    lines: list[str] = []
    for fname in sorted(families):
        fam = families[fname]
        help_text = str(fam.get("help") or "").replace("\\", r"\\").replace(
            "\n", r"\n"
        )
        if help_text:
            lines.append(f"# HELP {fname} {help_text}")
        lines.append(f"# TYPE {fname} {fam.get('type') or 'untyped'}")
        for s in fam["samples"]:
            labels = ",".join(
                f'{k}="{_escape_label(v)}"'
                for k, v in sorted(s["labels"].items())
            )
            body = f"{s['name']}{{{labels}}}" if labels else s["name"]
            lines.append(f"{body} {_format_value(s['value'])}")
    return "\n".join(lines) + "\n"


class FleetAggregator:
    """Merges per-process ``collect_families()`` snapshots into one fleet
    plane (driven by the Serve controller, one ``ingest`` per polled
    replica/proxy report).

    - Every source's samples are RELABELED with its entity labels
      (``deployment``/``replica_id``/``pool_role``/...), so per-replica
      series stay distinct at the single scrape target.
    - Rollup series drop ``replica_id`` and merge across sources with
      per-kind semantics: counters and histogram ``_bucket``/``_sum``/
      ``_count`` samples SUM (bucket counts are preserved exactly);
      gauges (and untyped families) are LAST-WRITE in report-stamp order.
    - Each relabeled series also feeds a bounded ring-buffer history
      (``history_samples`` newest ``(stamp, value)`` points, stamped with
      the ingest stamp — the controller's ``obs.clock``). Sources are
      never forgotten: a killed replica's last snapshot keeps the fleet
      counters monotonic and its rings stay queryable post-mortem.
    """

    ROLLUP_DROP = ("replica_id",)

    def __init__(self, history_samples: int = 360):
        self.history_samples = max(1, int(history_samples))
        self._lock = threading.Lock()
        # source key -> {"stamp", "labels", "families"}; insertion order
        # is irrelevant — fleet merges sort by stamp
        self._sources: dict[str, dict] = {}
        # relabeled series key -> deque[(stamp, value)]
        self._history: dict[str, deque] = {}

    def ingest(
        self,
        source: str,
        families: dict[str, dict],
        labels: dict[str, str],
        stamp: float,
    ) -> None:
        """Replace ``source``'s snapshot and append every sample to its
        history ring. Empty label values are dropped (Prometheus treats
        absent and empty labels identically)."""
        labels = {str(k): str(v) for k, v in (labels or {}).items() if v}
        with self._lock:
            self._sources[str(source)] = {
                "stamp": float(stamp),
                "labels": labels,
                "families": families,
            }
            for fam in families.values():
                for s in fam["samples"]:
                    key = sample_key(s["name"], {**s["labels"], **labels})
                    ring = self._history.get(key)
                    if ring is None:
                        ring = deque(maxlen=self.history_samples)
                        self._history[key] = ring
                    ring.append((float(stamp), float(s["value"])))

    def sources(self) -> dict[str, dict]:
        """{source: {"stamp", "labels"}} — who has reported, and when."""
        with self._lock:
            return {
                src: {"stamp": rec["stamp"], "labels": dict(rec["labels"])}
                for src, rec in self._sources.items()
            }

    def fleet_families(self) -> dict[str, dict]:
        """One ``collect_families()``-shaped dict for the whole fleet:
        per-source relabeled samples first, then the rollup samples
        (``replica_id`` dropped, per-kind merge)."""
        with self._lock:
            recs = sorted(
                self._sources.values(), key=lambda rec: rec["stamp"]
            )
            recs = [
                {
                    "stamp": rec["stamp"],
                    "labels": dict(rec["labels"]),
                    "families": rec["families"],
                }
                for rec in recs
            ]
        fams: dict[str, dict] = {}
        # (family, sample name, rollup label items) -> merged value
        rollup: dict[tuple, float] = {}
        for rec in recs:  # stamp order => "last write" = newest report
            for fname, fam in rec["families"].items():
                out = fams.setdefault(
                    fname,
                    {
                        "type": fam.get("type") or "untyped",
                        "help": fam.get("help") or "",
                        "samples": [],
                    },
                )
                summed = out["type"] in ("counter", "histogram")
                for s in fam["samples"]:
                    labels = {**s["labels"], **rec["labels"]}
                    out["samples"].append(
                        {
                            "name": s["name"],
                            "labels": labels,
                            "value": float(s["value"]),
                        }
                    )
                    if not any(k in labels for k in self.ROLLUP_DROP):
                        # nothing to drop: the per-source series IS the
                        # rollup; emitting both would duplicate it
                        continue
                    rl = tuple(sorted(
                        (k, v) for k, v in labels.items()
                        if k not in self.ROLLUP_DROP
                    ))
                    key = (fname, s["name"], rl)
                    if summed:
                        rollup[key] = rollup.get(key, 0.0) + float(s["value"])
                    else:
                        rollup[key] = float(s["value"])
        for (fname, sname, rl) in sorted(rollup, key=str):
            fams[fname]["samples"].append(
                {"name": sname, "labels": dict(rl), "value": rollup[(fname, sname, rl)]}
            )
        return fams

    def fleet_text(self) -> str:
        return render_prometheus(self.fleet_families())

    def history(
        self, series: str | None = None, prefix: str | None = None
    ) -> dict[str, list[tuple[float, float]]]:
        """Ring-buffer time series: ``{series_key: [(stamp, value), ...]}``
        (oldest first). ``series`` selects one exact key (``sample_key``
        spelling); ``prefix`` filters by key prefix; neither returns
        everything. Killed sources' rings remain until process exit."""
        with self._lock:
            if series is not None:
                ring = self._history.get(series)
                return {series: list(ring)} if ring is not None else {}
            return {
                key: list(ring)
                for key, ring in self._history.items()
                if prefix is None or key.startswith(prefix)
            }
