#!/usr/bin/env python
"""Docs-drift guard: the registry and the docs must name the same policies.

Fails (exit 1 / non-empty problem list) when:
  * a policy registered in ``repro.api.registry`` is missing from the
    registry table in ``docs/api.md`` — the failure mode this guards
    against is PR-1's: two policies were added to the registry and the
    docs table silently fell behind;
  * a documented kernel-path checkmark disagrees with the policy's actual
    ``kernel_inputs`` capability;
  * a kernel-hooked policy is missing from the "Built-in mappings" table
    in ``docs/kernels.md`` (every policy on the kernel path must document
    how its math maps onto the kernel template);
  * the admission core exposes wavefront batched admission but
    ``docs/kernels.md`` lost its "Batched wavefront admission" section;
  * the kernel package exposes the top-K candidate primitive but
    ``docs/kernels.md`` lost its "Top-K candidate lists" section;
  * ``SimConfig`` carries admission-path knobs (``admission_mode``,
    ``kernel_interpret``) or estimator/reclamation knobs (``estimator``,
    ``reclamation``, ``reclaim_margin``, ``reclaim_pool``) that
    ``docs/api.md`` does not document;
  * an estimator registered in ``repro.estimators`` is missing from the
    "Estimators" table in ``docs/api.md`` (or the table lists a name
    that is not registered);
  * ``docs/api.md`` lost its "Serving" section, or an ``EngineConfig``
    knob (serving engine) is undocumented there, or ``docs/kernels.md``
    stops mentioning the wavefront path's two front-ends (simulator
    scan + serving engine);
  * ``docs/api.md`` lost its "Faults & degradation" section, a
    ``FaultConfig`` knob is undocumented there, or ``docs/kernels.md``
    stops mentioning that fault eviction rides the shared admission
    path (``mask_unavailable`` load offsets);
  * ``docs/api.md`` lost its "Migration" section, a ``MigrationConfig``
    knob is undocumented there, or ``docs/kernels.md`` lost the
    "Source-exclusion cap" note (why the migrate pass excludes source
    nodes via node-side reserved offsets);
  * ``docs/api.md`` lost its "Guard" section, a ``GuardConfig`` knob
    (drift watchdog / circuit breaker) is undocumented there, or
    ``docs/kernels.md`` lost the "Confidence-scaled cap" note (how the
    guard's error quantile rides the reclaim/migrate cap scalar);
  * a cross-linked docs file (``docs/kernels.md``) has gone missing.

Run standalone (``python scripts/check_docs.py``) or through the tier-1
test suite (``tests/test_docs.py`` imports and asserts ``problems()``).
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def _registry_table_rows(api_md: str) -> dict:
    """Parse the 'Built-in registry' table: name -> kernel-path cell."""
    rows = {}
    in_section = False
    for line in api_md.splitlines():
        if line.startswith("## "):
            in_section = line.strip() == "## Built-in registry"
            continue
        if not in_section:
            continue
        m = re.match(r"\|\s*`([^`]+)`\s*\|[^|]*\|([^|]*)\|", line)
        if m:
            rows[m.group(1)] = m.group(2).strip()
    return rows


def _estimator_table_names(api_md: str) -> set:
    """Estimator names in the 'Estimators' table of docs/api.md."""
    names = set()
    in_section = False
    for line in api_md.splitlines():
        if line.startswith("## "):
            in_section = line.strip() == "## Estimators"
            continue
        if in_section:
            m = re.match(r"\|\s*`([^`]+)`\s*\|", line)
            if m:
                names.add(m.group(1))
    return names


def _kernel_mapping_names(kernels_md: str) -> set:
    """Policy names in the 'Built-in mappings' table of docs/kernels.md."""
    names = set()
    in_table = False
    for line in kernels_md.splitlines():
        if line.startswith("Built-in mappings"):
            in_table = True
            continue
        if in_table and line.startswith("#"):
            break
        if in_table and line.startswith("|"):
            first_cell = line.split("|")[1]
            names.update(re.findall(r"`([^`]+)`", first_cell))
    return names


def problems() -> list:
    """Return a list of human-readable drift descriptions (empty = clean)."""
    from repro.api import admission, get_policy, list_policies, \
        policy_supports_kernel

    out = []
    api_md_path = ROOT / "docs" / "api.md"
    if not api_md_path.exists():
        return [f"missing {api_md_path}"]
    api_md = api_md_path.read_text()
    kernels_md_path = ROOT / "docs" / "kernels.md"
    kernels_md = ""
    if not kernels_md_path.exists():
        out.append("docs/kernels.md is cross-linked from docs/api.md "
                   "but does not exist")
    else:
        kernels_md = kernels_md_path.read_text()
        if (hasattr(admission, "admit_queue_wavefront")
                and "## Batched wavefront admission" not in kernels_md):
            out.append(
                "repro.api.admission exposes admit_queue_wavefront but "
                "docs/kernels.md has no 'Batched wavefront admission' "
                "section")
        from repro.kernels import flex_score as _fs
        if (hasattr(_fs, "flex_pick_node_batch_topk")
                and "## Top-K candidate lists" not in kernels_md):
            out.append(
                "repro.kernels.flex_score exposes flex_pick_node_batch_topk "
                "but docs/kernels.md has no 'Top-K candidate lists' section")

    from repro.core.types import SimConfig
    for knob in ("admission_mode", "kernel_interpret",
                 "estimator", "reclamation", "reclaim_margin",
                 "reclaim_pool", "retry_backoff", "retry_backoff_cap",
                 "retry_jitter", "faults", "migration", "guard"):
        if knob in SimConfig._fields and f"`{knob}`" not in api_md:
            out.append(
                f"SimConfig field {knob!r} is not documented in docs/api.md")

    # Fault injection: every FaultConfig knob must appear in the
    # "Faults & degradation" section of docs/api.md — the fault surface
    # is config-driven, so an undocumented knob is an invisible one —
    # and docs/kernels.md must keep the note that fault eviction rides
    # the shared admission core (mask_unavailable), not a side path.
    from repro.faults import FaultConfig
    if "## Faults & degradation" not in api_md:
        out.append("docs/api.md has no '## Faults & degradation' section "
                   "but repro.faults exposes the fault-injection API")
    for knob in FaultConfig._fields:
        if f"`{knob}`" not in api_md:
            out.append(
                f"FaultConfig knob {knob!r} is not documented in "
                f"docs/api.md")
    if kernels_md and "fault eviction" not in kernels_md:
        out.append(
            "docs/kernels.md does not mention that fault eviction reuses "
            "the shared admission path (mask_unavailable load offsets)")

    # Live migration: every MigrationConfig knob must appear in the
    # "Migration" section of docs/api.md, and docs/kernels.md must keep
    # the "Source-exclusion cap" note — it documents WHY per-task source
    # exclusion rides a node-side reserved offset (the wavefront/dedup
    # invariants a straight per-task node plane would break).
    from repro.migration import MigrationConfig
    if "## Migration" not in api_md:
        out.append("docs/api.md has no '## Migration' section but "
                   "repro.migration exposes the live-migration API")
    for knob in MigrationConfig._fields:
        if f"`{knob}`" not in api_md:
            out.append(
                f"MigrationConfig knob {knob!r} is not documented in "
                f"docs/api.md")
    if kernels_md and "Source-exclusion cap" not in kernels_md:
        out.append(
            "docs/kernels.md lost its 'Source-exclusion cap' note (how "
            "the migrate pass excludes source nodes through node-side "
            "DRAIN_LOAD reserved offsets, wavefront/dedup sound)")

    # Drift guard: every GuardConfig knob must appear in the "Guard"
    # section of docs/api.md (the breaker's trip/cooldown/probe behavior
    # is entirely knob-driven), and docs/kernels.md must keep the
    # "Confidence-scaled cap" note — it documents why the guard's
    # continuous tightening is a slot-constant cap scalar (wavefront
    # sound), not new kernel machinery.
    from repro.guard import GuardConfig
    if "## Guard" not in api_md:
        out.append("docs/api.md has no '## Guard' section but "
                   "repro.guard exposes the drift-watchdog API")
    for knob in GuardConfig._fields:
        if f"`{knob}`" not in api_md:
            out.append(
                f"GuardConfig knob {knob!r} is not documented in "
                f"docs/api.md")
    if kernels_md and "Confidence-scaled cap" not in kernels_md:
        out.append(
            "docs/kernels.md lost its 'Confidence-scaled cap' note (how "
            "the guard's drift quantile scales the penalty riding the "
            "reclaim/migrate cap scalar, slot-constant)")

    # Serving engine: every EngineConfig knob must be documented in the
    # "Serving" section of docs/api.md (the knob set grew with the
    # wavefront front-end; undocumented knobs are exactly how the
    # batched-admission tuning surface would silently drift).
    import dataclasses as _dc
    from repro.serving.engine import EngineConfig
    if "## Serving" not in api_md:
        out.append("docs/api.md has no '## Serving' section but "
                   "repro.serving exposes the engine/stream API")
    for field in _dc.fields(EngineConfig):
        if f"`{field.name}`" not in api_md:
            out.append(
                f"EngineConfig knob {field.name!r} is not documented in "
                f"docs/api.md")
    if ("admit_queue" in dir(admission)
            and "front-end" not in kernels_md.lower()):
        out.append(
            "docs/kernels.md does not mention the wavefront path's two "
            "front-ends (simulator scan + serving engine)")

    from repro.estimators import list_estimators
    est_table = _estimator_table_names(api_md)
    for name in list_estimators():
        if name not in est_table:
            out.append(
                f"estimator {name!r} is registered but missing from the "
                f"'Estimators' table in docs/api.md")
    for name in est_table:
        if name not in list_estimators():
            out.append(
                f"docs/api.md Estimators table lists {name!r}, which is "
                f"not registered")

    table = _registry_table_rows(api_md)
    for name in list_policies():
        if name not in table:
            out.append(
                f"policy {name!r} is registered but missing from the "
                f"'Built-in registry' table in docs/api.md")
            continue
        documented_kernel = "✓" in table[name]
        actual_kernel = policy_supports_kernel(get_policy(name))
        if documented_kernel != actual_kernel:
            out.append(
                f"policy {name!r}: docs/api.md kernel-path column says "
                f"{'✓' if documented_kernel else '—'} but "
                f"kernel_inputs hook is "
                f"{'present' if actual_kernel else 'absent'}")
    for name in table:
        if name not in list_policies():
            out.append(
                f"docs/api.md registry table lists {name!r}, which is "
                f"not registered")

    mapping = _kernel_mapping_names(kernels_md)
    for name in list_policies():
        if policy_supports_kernel(get_policy(name)) and name not in mapping:
            out.append(
                f"policy {name!r} has a kernel_inputs hook but is missing "
                f"from the 'Built-in mappings' table in docs/kernels.md")
    return out


def main() -> int:
    probs = problems()
    for p in probs:
        print(f"docs drift: {p}", file=sys.stderr)
    if not probs:
        print("docs in sync with registry "
              "(policies documented, kernel flags correct)")
    return 1 if probs else 0


if __name__ == "__main__":
    raise SystemExit(main())
