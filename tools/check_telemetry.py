#!/usr/bin/env python
"""Validate telemetry artifacts: a JSONL span trace + a Prometheus snapshot.

    python tools/check_telemetry.py --trace telemetry/trace.jsonl \
        --metrics telemetry/metrics.prom

Checks, exiting nonzero on the first failure:

  * the trace parses line-by-line as JSON objects carrying the span schema
    (``name``/``ts_us``/``dur_us``/``tid``/``depth``) with non-negative
    durations and depths (names are the taxonomy in
    ``repro.obs.instrument.SPAN_NAMES``, JAX's compile events as
    ``jit.trace``/``jit.lower``/``jit.compile`` among them);
  * the metrics file is well-formed Prometheus text exposition: every
    sample is preceded by ``# HELP`` / ``# TYPE`` comments for its metric,
    sample lines match ``name{labels} value``, histogram ``_bucket``
    series are cumulative in ``le`` and end with ``+Inf`` equal to
    ``_count``;
  * (optional) ``--require-spans`` / ``--require-metrics`` assert that
    specific span names / metric names actually occur.

Run after an instrumented search (``--trace-out`` / ``--metrics-out`` on
``repro.launch.search``) -- CI does exactly that and uploads the artifacts.
"""
from __future__ import annotations

import argparse
import json
import re
import sys

SPAN_REQUIRED_KEYS = ("name", "ts_us", "dur_us", "tid", "depth")

# name{labels} value  -- labels optional; value is any float repr.  The
# labels group is greedy up to the LAST closing brace: label values may
# themselves contain braces (e.g. route="/v1/search/{uid}").
_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?P<labels>\{.*\})?'
    r' (?P<value>[0-9eE+.inf-]+)$')
_LABEL_RE = re.compile(r'(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>[^"]*)"')


def fail(msg: str) -> None:
    print(f"check_telemetry: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check_trace(path: str, require_spans) -> int:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    if not lines:
        fail(f"{path}: empty trace")
    seen = set()
    for i, ln in enumerate(lines, 1):
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError as e:
            fail(f"{path}:{i}: not JSON: {e}")
        if not isinstance(rec, dict):
            fail(f"{path}:{i}: span record is not an object")
        for k in SPAN_REQUIRED_KEYS:
            if k not in rec:
                fail(f"{path}:{i}: span missing key {k!r}: {rec}")
        if rec["dur_us"] < 0:
            fail(f"{path}:{i}: negative duration: {rec}")
        if rec["depth"] < 0:
            fail(f"{path}:{i}: negative depth: {rec}")
        seen.add(rec["name"])
    for name in require_spans:
        if name not in seen:
            fail(f"{path}: required span {name!r} never recorded "
                 f"(saw: {sorted(seen)})")
    print(f"check_telemetry: {path}: {len(lines)} spans OK "
          f"({len(seen)} distinct names)")
    return len(lines)


def _parse_value(s: str) -> float:
    if s == "+Inf":
        return float("inf")
    return float(s)


def check_metrics(path: str, require_metrics) -> int:
    with open(path) as f:
        lines = f.read().splitlines()
    if not lines:
        fail(f"{path}: empty metrics file")
    helped, typed = set(), {}
    samples = []   # (name, labels dict, value)
    for i, ln in enumerate(lines, 1):
        if not ln.strip():
            continue
        if ln.startswith("# HELP "):
            helped.add(ln.split()[2])
            continue
        if ln.startswith("# TYPE "):
            parts = ln.split()
            if len(parts) != 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"):
                fail(f"{path}:{i}: malformed TYPE line: {ln!r}")
            typed[parts[2]] = parts[3]
            continue
        if ln.startswith("#"):
            continue
        m = _SAMPLE_RE.match(ln)
        if m is None:
            fail(f"{path}:{i}: malformed sample line: {ln!r}")
        labels = {}
        if m.group("labels"):
            labels = {g.group("k"): g.group("v")
                      for g in _LABEL_RE.finditer(m.group("labels"))}
        try:
            value = _parse_value(m.group("value"))
        except ValueError:
            fail(f"{path}:{i}: bad sample value: {ln!r}")
        samples.append((m.group("name"), labels, value))

    if not samples:
        fail(f"{path}: no samples")

    # Every sample must belong to a declared metric (sample name == the
    # declared name or declared name + _total/_bucket/_sum/_count).
    def base_of(name: str):
        for base in typed:
            if name == base or (name.startswith(base) and name[len(base):]
                                in ("_total", "_bucket", "_sum", "_count")):
                return base
        return None

    for name, _, _ in samples:
        base = base_of(name)
        if base is None:
            fail(f"{path}: sample {name!r} has no # TYPE declaration")
        if base not in helped:
            fail(f"{path}: metric {base!r} has no # HELP line")

    # Histogram buckets: cumulative in le, +Inf present and == _count.
    hists = {n for n, k in typed.items() if k == "histogram"}
    for h in hists:
        series = {}   # non-le labels -> [(le, v)]
        counts = {}
        for name, labels, v in samples:
            if name == f"{h}_bucket":
                le = labels.get("le")
                if le is None:
                    fail(f"{path}: {name} sample missing le label")
                key = tuple(sorted((k, lv) for k, lv in labels.items()
                                   if k != "le"))
                series.setdefault(key, []).append((_parse_value(le), v))
            elif name == f"{h}_count":
                key = tuple(sorted(labels.items()))
                counts[key] = v
        for key, buckets in series.items():
            buckets.sort(key=lambda t: t[0])
            values = [v for _, v in buckets]
            if values != sorted(values):
                fail(f"{path}: {h}{dict(key)}: buckets not cumulative")
            if buckets[-1][0] != float("inf"):
                fail(f"{path}: {h}{dict(key)}: no +Inf bucket")
            if key in counts and buckets[-1][1] != counts[key]:
                fail(f"{path}: {h}{dict(key)}: +Inf bucket "
                     f"{buckets[-1][1]} != _count {counts[key]}")

    for name in require_metrics:
        if not any(base_of(n) == name for n, _, _ in samples):
            fail(f"{path}: required metric {name!r} has no samples")
    print(f"check_telemetry: {path}: {len(samples)} samples across "
          f"{len(typed)} metrics OK")
    return len(samples)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", default="",
                    help="JSONL span trace to validate")
    ap.add_argument("--metrics", default="",
                    help="Prometheus text exposition file to validate")
    ap.add_argument("--require-spans", default="",
                    help="comma list of span names that must appear")
    ap.add_argument("--require-metrics", default="",
                    help="comma list of metric names that must have samples")
    args = ap.parse_args(argv)
    if not args.trace and not args.metrics:
        ap.error("nothing to check: pass --trace and/or --metrics")
    if args.trace:
        check_trace(args.trace,
                    [s for s in args.require_spans.split(",") if s])
    if args.metrics:
        check_metrics(args.metrics,
                      [s for s in args.require_metrics.split(",") if s])
    print("check_telemetry: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
