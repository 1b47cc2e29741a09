#!/usr/bin/env python3
"""Static lint for metrics-registry instrument names.

Walks every registration call site (``<reg>.counter("...")`` /
``.gauge("...")`` / ``.histogram("...")`` with a literal name) under
``blaze_tpu/`` and ``scripts/`` and enforces:

1. every name matches the ``blaze_<area>_<name>_<unit>`` convention with a
   unit from ``telemetry.ALLOWED_UNITS`` (same check the registry applies at
   runtime — this catches names on paths tests never execute);
2. no two call sites register the same name via different instrument types
   (the runtime would raise on whichever loses the import race; the lint
   reports both locations deterministically);
3. every field the stats plane emits into QueryProfile JSON
   (``obs.stats.ALL_PROFILE_FIELDS``) is snake_case — profiles are an
   external artifact surface (HTTP, bench records, the on-disk store), so
   field names are API;
4. the attribution taxonomy (``obs.attribution``) is internally
   consistent: categories snake_case and unique, the priority sweep order
   a permutation of them, every category carrying a Chrome-trace color
   and a ``<category>_time_ns`` artifact field, and the fusion-break /
   placement-decline reason vocabularies snake_case — these strings land
   verbatim in artifacts and metric labels, so they are API too.

Tests are deliberately NOT scanned: they register intentionally-bad names
to assert the runtime validation. Standalone: exits 1 with a report on any
violation. Also run by ``tests/test_telemetry.py`` in the quick tier.
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIRS = ("blaze_tpu", "scripts")
METHODS = ("counter", "gauge", "histogram")


def iter_registrations(root: str):
    """Yield (path, lineno, method, name) for literal-name registrations."""
    for scan in SCAN_DIRS:
        base = os.path.join(root, scan)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path) as f:
                    try:
                        tree = ast.parse(f.read(), filename=path)
                    except SyntaxError as exc:
                        yield (path, exc.lineno or 0, "syntax", str(exc))
                        continue
                for node in ast.walk(tree):
                    if not (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr in METHODS
                            and node.args
                            and isinstance(node.args[0], ast.Constant)
                            and isinstance(node.args[0].value, str)):
                        continue
                    name = node.args[0].value
                    if not name.startswith("blaze_"):
                        continue  # MetricNode.timer etc. — not registry names
                    yield (os.path.relpath(path, root), node.lineno,
                           node.func.attr, name)


def run_lint(root: str = REPO):
    """Returns a list of violation strings (empty = clean)."""
    sys.path.insert(0, root)
    from blaze_tpu.obs.telemetry import validate_name

    violations = []
    seen = {}  # name -> (method, where)
    count = 0
    for path, lineno, method, name in iter_registrations(root):
        where = f"{path}:{lineno}"
        if method == "syntax":
            violations.append(f"{where}: unparseable: {name}")
            continue
        count += 1
        try:
            validate_name(name)
        except ValueError as exc:
            violations.append(f"{where}: {exc}")
        prev = seen.get(name)
        if prev is not None and prev[0] != method:
            violations.append(
                f"{where}: {name!r} registered as {method} but as "
                f"{prev[0]} at {prev[1]}")
        else:
            seen.setdefault(name, (method, where))
    if count == 0:
        violations.append("no registrations found — scan roots wrong?")
    violations.extend(check_profile_fields())
    violations.extend(check_attribution_taxonomy())
    violations.extend(check_cache_instruments(seen))
    violations.extend(check_timeline_taxonomy(seen))
    return violations


def check_cache_instruments(seen: dict):
    """The cache instrument families are a dashboard contract (ISSUE 19):
    every one of the five blaze_cache_* families must stay registered
    somewhere in the scanned tree — a rename or deletion silently breaks
    hit-rate panels and the soak tripwires that scrape them."""
    violations = []
    names = list(seen)
    for prefix in ("blaze_cache_hits_", "blaze_cache_misses_",
                   "blaze_cache_evictions_", "blaze_cache_stale_"):
        if not any(n.startswith(prefix) for n in names):
            violations.append(
                f"no registration found for required cache instrument "
                f"family {prefix}*")
    if not any(n.startswith("blaze_cache_") and "bytes" in n for n in names):
        violations.append(
            "no registration found for required cache instrument family "
            "blaze_cache_*bytes*")
    return violations


def check_timeline_taxonomy(seen: dict):
    """Validate the health plane (ISSUE 20): the blaze_timeline_* /
    blaze_slo_* instrument families must stay registered, and the
    timeline's vocabularies — subsystems, health states, derived series
    names, health-artifact fields — are API (they land verbatim in soak
    artifacts, /debug/health responses, and metric labels), so they must
    be snake_case (dots allowed in series names: ``<series>.<tenant>``
    variants), unique, and internally consistent."""
    import re

    try:
        from blaze_tpu.obs import timeline as tl
    except Exception as exc:
        return [f"obs.timeline unimportable: {exc}"]
    violations = []
    names = list(seen)
    for prefix in ("blaze_timeline_samples_", "blaze_timeline_sample_",
                   "blaze_timeline_series_", "blaze_slo_breaches_",
                   "blaze_slo_transitions_"):
        if not any(n.startswith(prefix) for n in names):
            violations.append(
                f"no registration found for required health-plane "
                f"instrument family {prefix}*")
    snake = re.compile(r"^[a-z][a-z0-9_]*$")
    for vocab_name, vocab in (
            ("SUBSYSTEMS", tl.SUBSYSTEMS),
            ("HEALTH_STATES", tl.HEALTH_STATES),
            ("DERIVED_SERIES", tl.DERIVED_SERIES),
            ("HEALTH_FIELDS", tl.HEALTH_FIELDS)):
        if len(set(vocab)) != len(vocab):
            violations.append(f"obs/timeline.py: duplicate in {vocab_name}")
        for v in vocab:
            if not snake.match(v):
                violations.append(
                    f"obs/timeline.py: {vocab_name} entry {v!r}"
                    " is not snake_case")
    for s in tl.COUNTER_TRACK_SERIES:
        if s not in tl.DERIVED_SERIES:
            violations.append(
                f"obs/timeline.py: COUNTER_TRACK_SERIES entry {s!r} not "
                f"in DERIVED_SERIES — the Chrome counter track would "
                f"sample a series the timeline never produces")
    for hs in ("healthy", "degraded", "critical"):
        if hs not in tl.HEALTH_STATES:
            violations.append(
                f"obs/timeline.py: HEALTH_STATES missing {hs!r} — the "
                f"state machine vocabulary is a gate contract")
    # every derived series leads with the subsystem it reports on, so a
    # reader (and the slo_specs grammar) can route it without a table
    known = set(tl.SUBSYSTEMS) | {"worker"}
    for s in tl.DERIVED_SERIES:
        if s.split("_", 1)[0] not in known:
            violations.append(
                f"obs/timeline.py: derived series {s!r} does not lead "
                f"with a subsystem prefix from SUBSYSTEMS")
    return violations


def check_profile_fields():
    """Validate the stats plane's QueryProfile field names: snake_case,
    no duplicates within one record schema."""
    import re

    try:
        from blaze_tpu.obs import stats
    except Exception as exc:  # import must not take the lint down
        return [f"obs.stats unimportable: {exc}"]
    snake = re.compile(r"^[a-z][a-z0-9_]*$")
    violations = []
    schemas = [
        ("PROFILE_FIELDS", stats.PROFILE_FIELDS),
        ("STAGE_FIELDS", stats.STAGE_FIELDS),
        ("OPERATOR_FIELDS", stats.OPERATOR_FIELDS),
        ("RESIDENCY_FIELDS", stats.RESIDENCY_FIELDS),
        ("SPILL_FIELDS", stats.SPILL_FIELDS),
        ("RECOVERY_FIELDS", stats.RECOVERY_FIELDS),
        ("ATTRIBUTION_FIELDS", stats.ATTRIBUTION_FIELDS),
        ("CRITICAL_PATH_FIELDS", stats.CRITICAL_PATH_FIELDS),
        ("AUDIT_FIELDS", stats.AUDIT_FIELDS),
        ("CACHE_FIELDS", stats.CACHE_FIELDS),
    ]
    for schema_name, fields in schemas:
        if len(set(fields)) != len(fields):
            violations.append(
                f"obs/stats.py: duplicate field in {schema_name}")
        for f in fields:
            if not snake.match(f):
                violations.append(
                    f"obs/stats.py: {schema_name} field {f!r}"
                    " is not snake_case")
    return violations


def check_attribution_taxonomy():
    """Validate the attribution plane's category/reason vocabularies —
    strings that appear verbatim in artifacts, metric labels, and the
    Chrome-trace color map, so internal consistency is an API contract."""
    import re

    try:
        from blaze_tpu.obs import attribution as attr
    except Exception as exc:
        return [f"obs.attribution unimportable: {exc}"]
    snake = re.compile(r"^[a-z][a-z0-9_]*$")
    violations = []
    cats = attr.CATEGORIES
    if len(set(cats)) != len(cats):
        violations.append("obs/attribution.py: duplicate in CATEGORIES")
    for c in cats:
        if not snake.match(c):
            violations.append(
                f"obs/attribution.py: category {c!r} is not snake_case")
    if sorted(attr.PRIORITY) != sorted(cats):
        violations.append(
            "obs/attribution.py: PRIORITY is not a permutation of "
            "CATEGORIES — the exclusivity sweep would drop or invent "
            "a category")
    missing_cname = [c for c in cats if c not in attr.CATEGORY_CNAME]
    if missing_cname:
        violations.append(
            f"obs/attribution.py: CATEGORY_CNAME missing {missing_cname}"
            " (uncolored spans in the Chrome trace)")
    if attr.CATEGORY_FIELDS != tuple(f"{c}_time_ns" for c in cats):
        violations.append(
            "obs/attribution.py: CATEGORY_FIELDS out of sync with "
            "CATEGORIES — artifact keys diverge from the taxonomy")
    for vocab_name, vocab in (
            ("FUSION_BREAK_REASONS", attr.FUSION_BREAK_REASONS),
            ("PLACEMENT_DECLINE_REASONS", attr.PLACEMENT_DECLINE_REASONS)):
        if len(set(vocab)) != len(vocab):
            violations.append(
                f"obs/attribution.py: duplicate in {vocab_name}")
        for r in vocab:
            if not snake.match(r):
                violations.append(
                    f"obs/attribution.py: {vocab_name} reason {r!r}"
                    " is not snake_case")
    try:
        from blaze_tpu.obs import stats
        for f in ("fused_op_fraction", "fusion_break_reasons"):
            if f not in stats.AUDIT_FIELDS:
                violations.append(
                    f"obs/stats.py: AUDIT_FIELDS missing {f!r} — the "
                    f"fusion-coverage tripwire left the profile schema")
    except Exception as exc:
        violations.append(f"obs.stats unimportable: {exc}")
    return violations


def main() -> int:
    violations = run_lint()
    if violations:
        print(f"check_metrics_names: {len(violations)} violation(s)")
        for v in violations:
            print(f"  {v}")
        return 1
    print("check_metrics_names: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
