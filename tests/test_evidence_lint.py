"""Static repo-hygiene lints in CI — thin wrapper over tools/lint.py.

1. Codebase lints: tools/lint.py runs its full pass suite (atomic
   durable-writes — migrated from this file's PR 4 version — plus
   thread-lifetime, swallowed-exception, and lock-held-across-blocking
   passes) over all of paddle_tpu/. Intentional sites carry
   `# lint-exempt:<pass>: <why>` annotations (the atomic pass also
   honors the legacy `# atomic-exempt`).
2. Lock order: tools/lockgraph.py finds no unexempted cycle.
3. Cache-writer positive check (ISSUE 6): the persistent compile cache
   and the serving warmstart artifact must publish via
   resilience.atomic.write_bytes.
4. Metric names PROFILE.md / SERVING.md mention exist in the registry.
"""

import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

from lint import WRITE_PATTERNS, lint_paths, pass_names  # noqa: E402


# -- codebase lint passes (tools/lint.py) ------------------------------------


@pytest.mark.parametrize("pass_name", pass_names())
def test_lint_pass_clean(pass_name):
    findings = lint_paths(passes=[pass_name])
    assert not findings, "\n".join(str(f) for f in findings)


# -- lock-order analysis (tools/lockgraph.py, ISSUE 13) ----------------------


def test_lockgraph_clean():
    """The interprocedural held->acquired graph over paddle_tpu/ has no
    unexempted cycles and no edges contradicting the committed
    tools/lock_order.json ledger. A failure here means a change
    introduced a potential lock-order inversion: fix the acquisition
    order, or justify it ('# lock-order-exempt: <why>' /
    a ledger exempt_edges entry) and regenerate the ledger with
    `tools/lockgraph.py --write-ledger`."""
    import lockgraph

    findings = lockgraph.analyze()
    assert not findings, "\n".join(str(f) for f in findings)


def lint_durable_writes():
    """Back-compat shim: PR 4 callers (and docs) reach the atomic pass
    through this name."""
    return [str(f) for f in lint_paths(passes=["atomic"])]


# -- compile-cache writer lint (ISSUE 6) -------------------------------------

# The persistent compile cache and the serving warmstart artifact are
# exactly the durable files a restart depends on: a torn entry turns
# every future restart into a corrupt-entry fallback, re-paying the
# compile the cache exists to kill.
_CACHE_WRITERS = ("paddle_tpu/core/compile_cache.py",
                  "paddle_tpu/serving/engine.py",
                  "paddle_tpu/serving/decode.py")


# -- metric-name drift (ISSUE 16) --------------------------------------------

# Docs whose `paddle_tpu_*` mentions are treated as metric-name claims.
_METRIC_DOCS = ("PROFILE.md", "SERVING.md")

# Every module that registers metrics at import time — importing these
# populates the default registry with the full live metric surface.
_INSTRUMENTED_MODULES = (
    "paddle_tpu.observability.telemetry",
    "paddle_tpu.observability.health",
    "paddle_tpu.observability.tracing",
    "paddle_tpu.observability.timeseries",
    "paddle_tpu.observability.slo",
    "paddle_tpu.core.compile_cache",
    "paddle_tpu.serving.engine",
    "paddle_tpu.serving.router",
    "paddle_tpu.serving.decode",
    "paddle_tpu.serving.kv_reuse",
    "paddle_tpu.serving.autoscale",
    "paddle_tpu.serving.httpd",
    "paddle_tpu.serving.qos",
    "paddle_tpu.serving.registry",
    "paddle_tpu.distributed.launch_serve",
    "paddle_tpu.observability.perfwatch",
    "paddle_tpu.observability.memwatch",
)

# Metrics this PR introduced: documentation is part of their contract.
_MUST_BE_DOCUMENTED = (
    "paddle_tpu_slo_burn_rate",
    "paddle_tpu_slo_alerts_total",
    "paddle_tpu_ts_samples_total",
    "paddle_tpu_mfu",
    "paddle_tpu_flops_per_sec",
    "paddle_tpu_steps_per_sec",
    "paddle_tpu_tokens_per_sec_per_chip",
    "paddle_tpu_step_time_seconds_total",
    "paddle_tpu_hbm_bytes",
    "paddle_tpu_hbm_buffers",
    "paddle_tpu_hbm_watermark_bytes",
    "paddle_tpu_hbm_budget_bytes",
    "paddle_tpu_executable_bytes",
    "paddle_tpu_oom_total",
    "paddle_tpu_prefix_cache_total",
    "paddle_tpu_decode_blocks_reused",
    "paddle_tpu_decode_spec_accept_rate",
    # multi-tenant QoS + model registry (ISSUE 19)
    "paddle_tpu_serving_sheds_total",
    "paddle_tpu_serving_tenant_requests_total",
    "paddle_tpu_serving_tenant_tokens_total",
    "paddle_tpu_serving_tenant_request_seconds",
    "paddle_tpu_decode_tenant_ttft_seconds",
    "paddle_tpu_model_version",
    "paddle_tpu_model_swaps_total",
    "paddle_tpu_registry_publishes_total",
    "paddle_tpu_fleet_sheds_total",
)


def test_documented_metric_names_match_registry():
    """A renamed metric silently orphans every dashboard/SLO built on
    the documented name: any `paddle_tpu_*` name PROFILE.md/SERVING.md
    mention must exist in the live registry after importing the
    instrumented modules, and the new time-series/SLO metrics must be
    documented."""
    import importlib
    import re

    for mod in _INSTRUMENTED_MODULES:
        importlib.import_module(mod)
    from paddle_tpu.observability import metrics as om

    live = set(om.snapshot())
    documented = set()
    for doc in _METRIC_DOCS:
        with open(os.path.join(_REPO, doc)) as f:
            documented |= set(re.findall(
                r"paddle_tpu_[a-z0-9_]*[a-z0-9]", f.read()))

    def base(name):
        # Prometheus exposition suffixes document the histogram itself
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name[:-len(suf)] in live:
                return name[:-len(suf)]
        return name

    documented = {base(n) for n in documented}
    missing = sorted(documented - live)
    assert not missing, (
        f"documented metric names missing from the live registry "
        f"(renamed without updating {'/'.join(_METRIC_DOCS)}?): "
        f"{missing}")
    undocumented = sorted(set(_MUST_BE_DOCUMENTED) - documented)
    assert not undocumented, (
        f"new telemetry metrics missing from {'/'.join(_METRIC_DOCS)}: "
        f"{undocumented}")


def test_cache_writers_route_through_atomic():
    for rel in _CACHE_WRITERS:
        path = os.path.join(_REPO, *rel.split("/"))
        with open(path) as f:
            src = f.read()
        assert "resilience.atomic import write_bytes" in src, \
            f"{rel}: cache writer must publish via " \
            f"resilience.atomic.write_bytes"
        for lineno, line in enumerate(src.splitlines(), 1):
            if "atomic-exempt" in line or "lint-exempt:atomic" in line:
                continue
            for pat, what in WRITE_PATTERNS:
                assert not pat.search(line), (
                    f"{rel}:{lineno}: cache writer uses bare {what} — "
                    f"publish through resilience.atomic.write_bytes: "
                    f"{line.strip()}")
