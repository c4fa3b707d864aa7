#!/usr/bin/env python
"""Docs consistency guard (run by the CI `docs` job and by
tests/test_docs.py, one case per check in ``CHECKS``).

Nine checks, so documentation cannot silently drift from the code:

1. Every relative markdown link in README.md and docs/*.md resolves to
   an existing file or directory.
2. Every backend name in the live engine registry
   (`repro.api.available_backends()`) appears as a row in the backend
   table of docs/ARCHITECTURE.md — registering a backend without
   documenting it fails the build.
3. The update-capability table in docs/ARCHITECTURE.md (rows of the
   form ``| `name` | scoped | ... |``) covers every registered backend
   and agrees with the live `repro.api.update_capabilities()` —
   misdeclaring how a backend absorbs hyperedge updates fails the
   build.
4. The serving request-type table in docs/ARCHITECTURE.md (rows of the
   form ``| `MRRequest` | `mr` | ... |``) matches the live
   `repro.serve.reach_service.REQUEST_TYPES` both ways — adding,
   renaming, or removing a request type without documenting it fails
   the build.
5. The construction-mode table in docs/ARCHITECTURE.md (rows of the
   form ``| `serial` | `build_fast` | ... |``) matches the live
   `repro.core.hlindex.CONSTRUCTION_MODES` both ways — documenting a
   builder option that does not exist, or adding one without
   documenting it, fails the build.
6. The on-disk format-version table in docs/ARCHITECTURE.md (rows of
   the form ``| `1` | `aligned-segments-v1` | ... |``) matches the live
   `repro.store.FORMAT_REGISTRY` both ways — shipping a format version
   the docs don't describe, or documenting one the code cannot read,
   fails the build.
7. The kernel-capability table in docs/ARCHITECTURE.md (rows of the
   form ``| `label_join` | `label_join_ref` | VPU | ... |``) matches
   the live `repro.kernels.KERNEL_REGISTRY` both ways — name, oracle,
   and compute unit; shipping a Pallas kernel without a doc row, or
   documenting one the registry does not have, fails the build.
8. The "Multi-tenant serving" section of docs/ARCHITECTURE.md matches
   the live scheduling surface both ways: its priority-class table
   (rows ``| `interactive` | 0 | ... |``) against
   `repro.serve.scheduler.PRIORITY_CLASSES` (names and band numbers),
   and its request-field table (rows ``| `tenant` | `str` |
   `"default"` | ... |``) against `dataclasses.fields(Request)` (names
   and defaults) — adding a priority class or a request metadata field
   without documenting it, or vice versa, fails the build.
9. The workload-capability table in the "Workloads" section of
   docs/ARCHITECTURE.md (header ``| backend | `witness` | ... |``,
   rows ``| `hl-index` | yes | ... |``) matches the live
   `repro.api.workload_capabilities()` both ways — the header must
   list exactly `WORKLOAD_OPS` in order, every registered backend
   needs a row, every row must agree cell-for-cell, and documenting a
   backend the registry does not have fails the build.

  PYTHONPATH=src python tools/check_docs.py
"""
from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_FENCE = re.compile(r"```.*?```", re.S)
_TABLE_ROW = re.compile(r"^\|\s*`([^`]+)`", re.M)
_CAPABILITY_ROW = re.compile(
    r"^\|\s*`([^`]+)`\s*\|\s*(scoped|incremental|rebuild|unsupported)\s*\|",
    re.M)
_REQUEST_ROW = re.compile(
    r"^\|\s*`(\w+Request)`\s*\|\s*`(\w+)`\s*\|", re.M)
_CONSTRUCTION_ROW = re.compile(
    r"^\|\s*`(\w+)`\s*\|\s*`(build_\w+)`\s*\|", re.M)
# a digit-only first cell is unique to the format-version table
_FORMAT_ROW = re.compile(r"^\|\s*`(\d+)`\s*\|\s*`([\w.-]+)`\s*\|", re.M)
# a `*_ref` second cell is unique to the kernel-capability table
_KERNEL_ROW = re.compile(
    r"^\|\s*`(\w+)`\s*\|\s*`(\w+_ref)`\s*\|\s*(\w+)\s*\|", re.M)
# the multi-tenant rows are scoped to their section (see _section), so
# these only need to be unique within it: a bare-integer second cell is
# the priority-class table, a backticked third cell the field table
_PRIORITY_ROW = re.compile(r"^\|\s*`(\w+)`\s*\|\s*(\d+)\s*\|", re.M)
_FIELD_ROW = re.compile(
    r"^\|\s*`(\w+)`\s*\|\s*`[^`]+`\s*\|\s*`([^`]+)`\s*\|", re.M)


def _section(text: str, title: str) -> str:
    """The body of one ``## title`` section (empty if absent)."""
    match = re.search(rf"^## {re.escape(title)}$(.*?)(?=^## |\Z)",
                      text, re.M | re.S)
    return match.group(1) if match else ""


def doc_files():
    docs = ROOT / "docs"
    return [ROOT / "README.md"] + (sorted(docs.glob("*.md"))
                                   if docs.is_dir() else [])


def check_links():
    problems = []
    for md in doc_files():
        text = _FENCE.sub("", md.read_text())
        for match in _LINK.finditer(text):
            target = match.group(1)
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            path = target.split("#")[0]
            if path and not (md.parent / path).exists():
                problems.append(
                    f"{md.relative_to(ROOT)}: broken link -> {target}")
    return problems


def check_backend_table():
    from repro.api import available_backends

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    # catalogue rows only: a row in the update-capability table (second
    # column is a capability word) must not satisfy this check, or
    # deleting a backend's catalogue row would go unnoticed
    documented = {name for line in arch.read_text().splitlines()
                  if (match := _TABLE_ROW.match(line)) is not None
                  and not _CAPABILITY_ROW.match(line)
                  for name in [match.group(1)]}
    return [f"docs/ARCHITECTURE.md backend table is missing registered "
            f"backend `{name}`"
            for name in available_backends() if name not in documented]


def check_update_capability_table():
    from repro.api import update_capabilities

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    documented = dict(_CAPABILITY_ROW.findall(arch.read_text()))
    problems = []
    for name, cap in update_capabilities().items():
        if name not in documented:
            problems.append(
                f"docs/ARCHITECTURE.md update-capability table is missing "
                f"registered backend `{name}` (declared: {cap})")
        elif documented[name] != cap:
            problems.append(
                f"docs/ARCHITECTURE.md declares `{name}` updates as "
                f"'{documented[name]}' but the registry says '{cap}'")
    return problems


def check_request_type_table():
    from repro.serve.reach_service import REQUEST_TYPES

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    documented = {kind: cls_name
                  for cls_name, kind in _REQUEST_ROW.findall(arch.read_text())}
    problems = []
    for kind, cls in REQUEST_TYPES.items():
        if kind not in documented:
            problems.append(
                f"docs/ARCHITECTURE.md request-type table is missing the "
                f"`{cls.__name__}` (kind `{kind}`) row")
        elif documented[kind] != cls.__name__:
            problems.append(
                f"docs/ARCHITECTURE.md documents kind `{kind}` as "
                f"`{documented[kind]}` but the live service class is "
                f"`{cls.__name__}`")
    for kind in documented:
        if kind not in REQUEST_TYPES:
            problems.append(
                f"docs/ARCHITECTURE.md documents request kind `{kind}` "
                f"(`{documented[kind]}`) that the live "
                f"repro.serve.reach_service.REQUEST_TYPES does not have")
    return problems


def check_construction_table():
    from repro.core.hlindex import CONSTRUCTION_MODES

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    documented = dict(_CONSTRUCTION_ROW.findall(arch.read_text()))
    problems = []
    for mode, fn in CONSTRUCTION_MODES.items():
        if mode not in documented:
            problems.append(
                f"docs/ARCHITECTURE.md construction table is missing the "
                f"`{mode}` (builder `{fn.__name__}`) row")
        elif documented[mode] != fn.__name__:
            problems.append(
                f"docs/ARCHITECTURE.md documents construction mode "
                f"`{mode}` as `{documented[mode]}` but the live builder "
                f"is `{fn.__name__}`")
    for mode in documented:
        if mode not in CONSTRUCTION_MODES:
            problems.append(
                f"docs/ARCHITECTURE.md documents construction mode "
                f"`{mode}` (`{documented[mode]}`) that the live "
                f"repro.core.hlindex.CONSTRUCTION_MODES does not have")
    return problems


def check_format_table():
    from repro.store import FORMAT_REGISTRY

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    documented = {int(v): layout
                  for v, layout in _FORMAT_ROW.findall(arch.read_text())}
    problems = []
    for version, layout in FORMAT_REGISTRY.items():
        if version not in documented:
            problems.append(
                f"docs/ARCHITECTURE.md format-version table is missing "
                f"on-disk format `{version}` (layout `{layout}`)")
        elif documented[version] != layout:
            problems.append(
                f"docs/ARCHITECTURE.md documents on-disk format "
                f"`{version}` as `{documented[version]}` but the live "
                f"repro.store.FORMAT_REGISTRY says `{layout}`")
    for version in documented:
        if version not in FORMAT_REGISTRY:
            problems.append(
                f"docs/ARCHITECTURE.md documents on-disk format "
                f"`{version}` (`{documented[version]}`) that the live "
                f"repro.store.FORMAT_REGISTRY cannot read")
    return problems


def check_kernel_table():
    from repro.kernels import KERNEL_REGISTRY

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    documented = {name: (oracle, unit)
                  for name, oracle, unit
                  in _KERNEL_ROW.findall(arch.read_text())}
    problems = []
    for name, spec in KERNEL_REGISTRY.items():
        if name not in documented:
            problems.append(
                f"docs/ARCHITECTURE.md kernel-capability table is missing "
                f"registered kernel `{name}` (oracle "
                f"`{spec.reference.__name__}`, unit {spec.unit})")
            continue
        oracle, unit = documented[name]
        if oracle != spec.reference.__name__:
            problems.append(
                f"docs/ARCHITECTURE.md documents kernel `{name}` with "
                f"oracle `{oracle}` but the registry says "
                f"`{spec.reference.__name__}`")
        if unit != spec.unit:
            problems.append(
                f"docs/ARCHITECTURE.md documents kernel `{name}` on unit "
                f"{unit} but the registry says {spec.unit}")
    for name in documented:
        if name not in KERNEL_REGISTRY:
            problems.append(
                f"docs/ARCHITECTURE.md documents kernel `{name}` that the "
                f"live repro.kernels.KERNEL_REGISTRY does not have")
    return problems


def check_multitenant_section():
    import dataclasses

    from repro.serve.reach_service import Request
    from repro.serve.scheduler import PRIORITY_CLASSES

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    body = _section(arch.read_text(), "Multi-tenant serving")
    if not body:
        return ["docs/ARCHITECTURE.md has no '## Multi-tenant serving' "
                "section"]
    problems = []

    documented_classes = {name: int(band)
                          for name, band in _PRIORITY_ROW.findall(body)}
    for name, band in PRIORITY_CLASSES.items():
        if name not in documented_classes:
            problems.append(
                f"docs/ARCHITECTURE.md priority-class table is missing "
                f"class `{name}` (band {band})")
        elif documented_classes[name] != band:
            problems.append(
                f"docs/ARCHITECTURE.md documents priority class `{name}` "
                f"as band {documented_classes[name]} but the live "
                f"PRIORITY_CLASSES says {band}")
    for name in documented_classes:
        if name not in PRIORITY_CLASSES:
            problems.append(
                f"docs/ARCHITECTURE.md documents priority class `{name}` "
                f"that the live repro.serve.scheduler.PRIORITY_CLASSES "
                f"does not have")

    # default shown with double quotes in the docs; repr() uses single
    documented_fields = {name: default.replace("'", '"')
                         for name, default in _FIELD_ROW.findall(body)}
    live_fields = {f.name: repr(f.default).replace("'", '"')
                   for f in dataclasses.fields(Request)}
    for name, default in live_fields.items():
        if name not in documented_fields:
            problems.append(
                f"docs/ARCHITECTURE.md request-field table is missing the "
                f"`{name}` (default {default}) row")
        elif documented_fields[name] != default:
            problems.append(
                f"docs/ARCHITECTURE.md documents request field `{name}` "
                f"with default {documented_fields[name]} but the live "
                f"Request dataclass says {default}")
    for name in documented_fields:
        if name not in live_fields:
            problems.append(
                f"docs/ARCHITECTURE.md documents request field `{name}` "
                f"that the live repro.serve.reach_service.Request does "
                f"not have")
    return problems


def check_workload_table():
    from repro.api import WORKLOAD_OPS, workload_capabilities

    arch = ROOT / "docs" / "ARCHITECTURE.md"
    if not arch.is_file():
        return ["docs/ARCHITECTURE.md is missing"]
    body = _section(arch.read_text(), "Workloads")
    if not body:
        return ["docs/ARCHITECTURE.md has no '## Workloads' section"]
    header = re.search(r"^\|\s*backend\s*\|(.+)\|\s*$", body, re.M)
    if header is None:
        return ["docs/ARCHITECTURE.md Workloads section has no "
                "'| backend | ...' capability table header"]
    doc_ops = tuple(re.findall(r"`(\w+)`", header.group(1)))
    if doc_ops != tuple(WORKLOAD_OPS):
        return [f"docs/ARCHITECTURE.md workload-capability table header "
                f"lists ops {list(doc_ops)} but the live WORKLOAD_OPS is "
                f"{list(WORKLOAD_OPS)}"]
    documented = {}
    for line in body.splitlines():
        row = re.match(r"^\|\s*`([\w-]+)`\s*\|(.+)\|\s*$", line)
        if row is None:
            continue
        cells = [c.strip() for c in row.group(2).split("|")]
        if len(cells) == len(doc_ops) and set(cells) <= {"yes", "no"}:
            documented[row.group(1)] = {
                op: cell == "yes" for op, cell in zip(doc_ops, cells)}
    problems = []
    live = workload_capabilities()
    for name, caps in live.items():
        if name not in documented:
            problems.append(
                f"docs/ARCHITECTURE.md workload-capability table is "
                f"missing registered backend `{name}`")
        elif documented[name] != caps:
            problems.append(
                f"docs/ARCHITECTURE.md workload-capability row for "
                f"`{name}` says {documented[name]} but the live registry "
                f"says {caps}")
    for name in documented:
        if name not in live:
            problems.append(
                f"docs/ARCHITECTURE.md workload-capability table "
                f"documents backend `{name}` that the live registry does "
                f"not have")
    return problems


CHECKS = (check_links, check_backend_table, check_update_capability_table,
          check_request_type_table, check_construction_table,
          check_format_table, check_kernel_table, check_multitenant_section,
          check_workload_table)


def main() -> int:
    problems = [p for check in CHECKS for p in check()]
    for p in problems:
        print(f"FAIL: {p}")
    if problems:
        return 1
    from repro.api import (available_backends, update_capabilities,
                           workload_capabilities)
    from repro.core.hlindex import CONSTRUCTION_MODES
    from repro.kernels import KERNEL_REGISTRY
    from repro.serve.reach_service import REQUEST_TYPES
    from repro.serve.scheduler import PRIORITY_CLASSES
    from repro.store import FORMAT_REGISTRY
    print(f"docs OK: links resolve in {len(doc_files())} files; "
          f"backend table covers {available_backends()}; update "
          f"capabilities match {update_capabilities()}; request types "
          f"match {sorted(REQUEST_TYPES)}; construction modes match "
          f"{sorted(CONSTRUCTION_MODES)}; on-disk formats match "
          f"{FORMAT_REGISTRY}; kernel table matches "
          f"{sorted(KERNEL_REGISTRY)}; multi-tenant section matches "
          f"{PRIORITY_CLASSES} and the Request metadata fields; workload "
          f"capabilities match for "
          f"{sorted(workload_capabilities())}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
