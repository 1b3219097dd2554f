"""Registry-drift audit: hand-maintained catalogs vs their sources.

Five registries in this repo are maintained by hand and consumed by
humans and machines alike — and before this checker nothing gated
them against their emit sites, docs, and drills:

* the **metrics-row field catalog** (``telemetry/schema.py``
  ``METRICS_REQUIRED``/``METRICS_OPTIONAL``) vs the fields the round
  loop and the subsystem gauge functions actually emit, and vs the
  metric-catalog tables ``docs/observability.md`` renders (FTC001);
* the **event-name list** in ``docs/observability.md`` vs every
  ``telemetry.event("...")`` emit site (FTC002);
* ``config.HOST_FAULT_SEAMS`` vs the chaos drill
  (``scripts/chaos_suite.py --host-fault-matrix``), the
  ``--host_fault_seams`` CLI help, and the seam table in
  ``docs/robustness.md`` (FTC003);
* the **config<->CLI surface**: every argparse dest ``cli.py``
  parses vs the ``args.*`` fields ``args_to_config`` consumes
  (FTC004);
* the **builder-cell matrix**: ``parallel/round_program.py``'s axis
  tuples vs ``tests/test_round_builder.py``'s ILLEGAL set and the
  per-cell refusal-message snapshots (FTC005).

Everything here is stdlib-only (``ast`` + text scans + imports of the
two deliberately jax-free modules, ``telemetry.schema`` and
``config``), so the checker runs in any CI lane — it is wired into
``scripts/lint_suite.py`` next to ruff and the AST analyzer, and into
``fedtorch-tpu audit`` next to the program audit. Each check is split
into EXTRACTION (source/docs -> name sets, unit-testable on seeded
text) and DIFF (pure set logic -> findings), so fixture tests seed
violations without a fake repo tree.

The checker ships with an empty baseline on purpose: registry drift
is always fixable at the registry or the emit site, so findings are
fixed, not accepted (docs/static_analysis.md "The registry audit").
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Set, Tuple

from fedtorch_tpu.lint.findings import Finding
from fedtorch_tpu.lint.rules import hint_for

# catalog entries intentionally without a live emit site (none today;
# a future reserved gauge goes here WITH a comment saying why)
RESERVED_METRIC_FIELDS: Tuple[str, ...] = ()

# argparse dests that are deliberately not config fields (consumed by
# main()/run_experiment directly, not args_to_config)
NON_CONFIG_DESTS: Tuple[str, ...] = ("download",)

# functions whose returned dict keys ride the metrics row
_GAUGE_FN_NAMES = {"stats", "telemetry_gauges", "round_gauges",
                   # a token model's host floats (models/common.py
                   # ``is_token_model``) and the counters under them
                   "trace_gauges", "kept_counters"}
# module-level dict literals whose keys ride the metrics row: a token
# model's table of its ``gauge_names``
_GAUGE_TABLE_NAMES = {"_PART_GAUGES"}


def _finding(path: str, line: int, rule: str, message: str,
             evidence: str = "") -> Finding:
    return Finding(path=path, line=line, col=0, rule=rule,
                   message=message, hint=hint_for(rule),
                   source_line=evidence)


def _read(root: str, rel: str) -> str:
    with open(os.path.join(root, rel), encoding="utf-8") as fh:
        return fh.read()


def _str_keys(node: ast.AST) -> List[str]:
    """String keys of a dict literal node."""
    out = []
    if isinstance(node, ast.Dict):
        for k in node.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                out.append(k.value)
    return out


# -- FTC001: metrics-row fields ------------------------------------------

def emitted_row_fields_from_source(src: str) -> Set[str]:
    """Field names one module contributes to the metrics row:

    * keys of the round loop's ``row = {...}`` literal,
      ``row["x"] = ...`` assignments, and ``row.update(x=..., {...})``;
    * keys of dict literals built/returned inside functions named
      in ``_GAUGE_FN_NAMES`` (the gauge providers the loop merges in),
      including ``out["x"] = ...`` and ``out.update({...}, x=...)``
      inside them;
    * keys of the module-level dict literals named in
      ``_GAUGE_TABLE_NAMES``.
    """
    tree = ast.parse(src)
    fields: Set[str] = set()

    def collect_updates(call: ast.Call) -> None:
        for kw in call.keywords:
            if kw.arg is not None:
                fields.add(kw.arg)
            else:
                fields.update(_str_keys(kw.value))
        for a in call.args:
            fields.update(_str_keys(a))

    # the row loop's direct writes, and the gauge tables
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and (
                        tgt.id == "row" or tgt.id in _GAUGE_TABLE_NAMES):
                    fields.update(_str_keys(node.value))
                if isinstance(tgt, ast.Subscript) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "row" and \
                        isinstance(tgt.slice, ast.Constant) and \
                        isinstance(tgt.slice.value, str):
                    fields.add(tgt.slice.value)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "update" and \
                isinstance(node.func.value, ast.Name) and \
                node.func.value.id == "row":
            collect_updates(node)

    # gauge-provider functions
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or node.name not in _GAUGE_FN_NAMES:
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Dict):
                fields.update(_str_keys(sub))
            elif isinstance(sub, ast.Assign):
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Subscript) and \
                            isinstance(tgt.slice, ast.Constant) and \
                            isinstance(tgt.slice.value, str):
                        fields.add(tgt.slice.value)
            elif isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr == "update":
                collect_updates(sub)
    return fields


_EMIT_SITE_FILES = (
    "fedtorch_tpu/cli.py",
    "fedtorch_tpu/parallel/federated.py",
    "fedtorch_tpu/async_plane/commit.py",
    # a token model's own gauges (models/common.py ``is_token_model``)
    "fedtorch_tpu/models/hybrid_lm.py",
    "fedtorch_tpu/data/streaming.py",
    "fedtorch_tpu/utils/checkpoint.py",
    "fedtorch_tpu/robustness/host_recovery.py",
    "fedtorch_tpu/robustness/host_chaos.py",
    "fedtorch_tpu/telemetry/costs.py",
    "fedtorch_tpu/telemetry/ledger.py",
    # the writer itself stamps every row (seq + t, ops plane)
    "fedtorch_tpu/telemetry/metrics.py",
)


def emitted_row_fields(root: str) -> Set[str]:
    fields: Set[str] = set()
    for rel in _EMIT_SITE_FILES:
        fields.update(emitted_row_fields_from_source(_read(root, rel)))
    return fields


def cataloged_row_fields() -> Set[str]:
    from fedtorch_tpu.telemetry.schema import all_metric_fields
    return set(all_metric_fields())


_BACKTICK_RE = re.compile(r"`([A-Za-z_][\w.]*)`")


def documented_row_fields(doc_text: str) -> Set[str]:
    """Field names the docs/observability.md metric catalog lists:
    backticked identifiers in the FIELDS column (second cell) of the
    optional-group table rows, plus the ``Required:`` line — prose
    backticks elsewhere in the section are not field claims."""
    lo = doc_text.find("## Metric catalog")
    hi = doc_text.find("## Span taxonomy")
    section = doc_text[lo:hi] if 0 <= lo < hi else doc_text
    fields: Set[str] = set()
    in_required = False
    for line in section.splitlines():
        stripped = line.strip()
        if stripped.startswith("Required:"):
            in_required = True
        elif not stripped:
            in_required = False
        if in_required:
            fields.update(_BACKTICK_RE.findall(stripped))
            continue
        if not stripped.startswith("|"):
            continue
        cells = stripped.split("|")
        if len(cells) >= 3 and "---" not in cells[1]:
            fields.update(_BACKTICK_RE.findall(cells[2]))
    return {f for f in fields if "." not in f and f == f.lower()
            and f not in ("group", "fields", "source")}


def diff_metric_fields(emitted: Set[str], cataloged: Set[str],
                       documented: Set[str],
                       reserved: Iterable[str] = RESERVED_METRIC_FIELDS
                       ) -> List[Finding]:
    out = []
    schema_path = "fedtorch_tpu/telemetry/schema.py"
    docs_path = "docs/observability.md"
    for f in sorted(emitted - cataloged):
        out.append(_finding(
            schema_path, 0, "FTC001",
            f"metrics-row field {f!r} is emitted but not cataloged in "
            "METRICS_REQUIRED/METRICS_OPTIONAL", f))
    for f in sorted(cataloged - emitted - set(reserved)):
        out.append(_finding(
            schema_path, 0, "FTC001",
            f"cataloged metrics-row field {f!r} has no emit site "
            "(and is not in RESERVED_METRIC_FIELDS)", f))
    for f in sorted(cataloged - documented):
        out.append(_finding(
            docs_path, 0, "FTC001",
            f"cataloged metrics-row field {f!r} is missing from the "
            "docs/observability.md metric-catalog tables", f))
    for f in sorted(documented - cataloged):
        out.append(_finding(
            docs_path, 0, "FTC001",
            f"docs/observability.md documents metrics-row field {f!r} "
            "that the schema does not catalog", f))
    return out


# -- FTC002: event names -------------------------------------------------

_EVENT_NAME_RE = re.compile(r"^[a-z_]+\.[a-z_]+$")


def emitted_event_names_from_source(src: str) -> Set[str]:
    """First string argument of every ``*.event("name", ...)`` call."""
    names: Set[str] = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "event" and node.args and \
                isinstance(node.args[0], ast.Constant) and \
                isinstance(node.args[0].value, str):
            names.add(node.args[0].value)
    return names


def emitted_event_names(root: str) -> Set[str]:
    names: Set[str] = set()
    pkg = os.path.join(root, "fedtorch_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, fn),
                       encoding="utf-8").read()
            for name in emitted_event_names_from_source(src):
                if _EVENT_NAME_RE.match(name):
                    names.add(name)
    return names


def documented_event_names(doc_text: str) -> Set[str]:
    """Backticked dotted names in the events paragraphs of
    docs/observability.md (between the 'Events (`events.jsonl`)'
    anchor and the span-taxonomy heading), minus file names."""
    lo = doc_text.find("Events (`events.jsonl`)")
    hi = doc_text.find("## Span taxonomy")
    section = doc_text[lo:hi] if 0 <= lo < hi else ""
    names = set()
    for m in _BACKTICK_RE.findall(section):
        if _EVENT_NAME_RE.match(m) and not m.endswith(
                (".md", ".py", ".json", ".jsonl", ".sh")):
            names.add(m)
    return names


def diff_event_names(emitted: Set[str], documented: Set[str]
                     ) -> List[Finding]:
    out = []
    docs_path = "docs/observability.md"
    for n in sorted(emitted - documented):
        out.append(_finding(
            docs_path, 0, "FTC002",
            f"event {n!r} is emitted but missing from the "
            "docs/observability.md event list", n))
    for n in sorted(documented - emitted):
        out.append(_finding(
            docs_path, 0, "FTC002",
            f"docs/observability.md lists event {n!r} with no emit "
            "site in the package", n))
    return out


# -- FTC003: host-fault seams --------------------------------------------

_SEAM_ROW_RE = re.compile(r"^\|\s*`([a-z]+\.[a-z0-9_]+)`\s*\|",
                          re.MULTILINE)


def documented_seams(robustness_md: str) -> Set[str]:
    """Seam names of the docs/robustness.md seam table (backticked
    first column)."""
    return set(_SEAM_ROW_RE.findall(robustness_md))


def seam_literals(src: str, seams: Iterable[str]) -> Set[str]:
    """Which of ``seams`` appear verbatim (as string content) in a
    source/doc text — used for the CLI help and drill coverage."""
    return {s for s in seams if s in src}


def check_seams(root: str) -> List[Finding]:
    from fedtorch_tpu.config import HOST_FAULT_SEAMS
    seams = set(HOST_FAULT_SEAMS)
    out: List[Finding] = []

    robustness = _read(root, "docs/robustness.md")
    documented = documented_seams(robustness)
    for s in sorted(seams - documented):
        out.append(_finding(
            "docs/robustness.md", 0, "FTC003",
            f"seam {s!r} has no row in the robustness.md seam table",
            s))
    # extra drill-only cells (stream.rebuild) are legal table-external
    # names; a documented seam the config does not know is drift
    for s in sorted(documented - seams):
        out.append(_finding(
            "docs/robustness.md", 0, "FTC003",
            f"robustness.md seam table names {s!r}, which is not in "
            "config.HOST_FAULT_SEAMS", s))

    cli_src = _read(root, "fedtorch_tpu/cli.py")
    for s in sorted(seams - seam_literals(cli_src, seams)):
        out.append(_finding(
            "fedtorch_tpu/cli.py", 0, "FTC003",
            f"seam {s!r} is missing from the --host_fault_seams help "
            "text", s))

    drill_src = _read(root, "scripts/chaos_suite.py")
    # the drill derives its axis from the config tuple itself — the
    # import is the coverage guarantee; without it, every seam would
    # need its own literal drill cell
    if "HOST_FAULT_SEAMS" not in drill_src:
        out.append(_finding(
            "scripts/chaos_suite.py", 0, "FTC003",
            "the host-fault drill no longer enumerates "
            "config.HOST_FAULT_SEAMS — new seams can land without a "
            "drill cell", "HOST_FAULT_SEAMS"))
    return out


# -- FTC004: config <-> CLI surface --------------------------------------

def parser_dests(src: str) -> Dict[str, int]:
    """argparse dest -> line for every ``add_argument`` call in
    ``build_parser``: the explicit ``dest=`` when given, else derived
    from the first long option."""
    dests: Dict[str, int] = {}
    for node in ast.walk(ast.parse(src)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        dest = None
        for kw in node.keywords:
            if kw.arg == "dest" and isinstance(kw.value, ast.Constant):
                dest = kw.value.value
        if dest is None:
            for a in node.args:
                if isinstance(a, ast.Constant) and \
                        isinstance(a.value, str) and \
                        a.value.startswith("--"):
                    dest = a.value[2:].replace("-", "_")
                    break
        if dest is not None:
            dests[dest] = node.lineno
    return dests


def consumed_args(src: str) -> Set[str]:
    """``args.X`` attribute loads inside ``args_to_config`` and
    ``main`` (the two consumers of the parsed namespace)."""
    tree = ast.parse(src)
    used: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in ("args_to_config", "main"):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Attribute) and \
                        isinstance(sub.value, ast.Name) and \
                        sub.value.id == "args":
                    used.add(sub.attr)
    return used


def diff_config_cli(dests: Dict[str, int], used: Set[str],
                    non_config: Iterable[str] = NON_CONFIG_DESTS
                    ) -> List[Finding]:
    out = []
    cli_path = "fedtorch_tpu/cli.py"
    for d in sorted(set(dests) - used - set(non_config)):
        out.append(_finding(
            cli_path, dests[d], "FTC004",
            f"CLI flag dest {d!r} is parsed but never consumed by "
            "args_to_config/main — the flag silently does nothing", d))
    for a in sorted(used - set(dests)):
        out.append(_finding(
            cli_path, 0, "FTC004",
            f"args_to_config reads args.{a} but no add_argument "
            "defines that dest — it raises AttributeError at run "
            "time", a))
    return out


def check_config_cli(root: str) -> List[Finding]:
    src = _read(root, "fedtorch_tpu/cli.py")
    return diff_config_cli(parser_dests(src), consumed_args(src))


# -- FTC005: builder-cell matrix -----------------------------------------

def axis_tuples(round_program_src: str) -> Dict[str, Tuple[str, ...]]:
    """The SOURCES/DISPATCHES/EXECUTIONS tuples, read off the AST so
    the checker never imports jax."""
    out: Dict[str, Tuple[str, ...]] = {}
    for node in ast.walk(ast.parse(round_program_src)):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id in ("SOURCES", "DISPATCHES",
                                           "EXECUTIONS") \
                and isinstance(node.value, ast.Tuple):
            out[node.targets[0].id] = tuple(
                e.value for e in node.value.elts
                if isinstance(e, ast.Constant))
    return out


def illegal_cells(test_src: str) -> Set[Tuple[str, str, str]]:
    """The ILLEGAL set literal in tests/test_round_builder.py."""
    cells: Set[Tuple[str, str, str]] = set()
    for node in ast.walk(ast.parse(test_src)):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "ILLEGAL" \
                and isinstance(node.value, ast.Set):
            for elt in node.value.elts:
                if isinstance(elt, ast.Tuple) and len(elt.elts) == 3:
                    cells.add(tuple(e.value for e in elt.elts))
    return cells


def diff_builder_cells(axes: Dict[str, Tuple[str, ...]],
                       illegal: Set[Tuple[str, str, str]],
                       test_src: str) -> List[Finding]:
    out = []
    rp_path = "fedtorch_tpu/parallel/round_program.py"
    test_path = "tests/test_round_builder.py"
    if set(axes) != {"SOURCES", "DISPATCHES", "EXECUTIONS"}:
        return [_finding(
            rp_path, 0, "FTC005",
            "could not read the SOURCES/DISPATCHES/EXECUTIONS axis "
            "tuples from round_program.py", str(sorted(axes)))]
    if not illegal:
        out.append(_finding(
            test_path, 0, "FTC005",
            "tests/test_round_builder.py no longer pins an ILLEGAL "
            "cell set — the refusal half of the matrix is ungated",
            "ILLEGAL"))
    for cell in sorted(illegal):
        s, d, e = cell
        if s not in axes["SOURCES"] or d not in axes["DISPATCHES"] \
                or e not in axes["EXECUTIONS"]:
            out.append(_finding(
                test_path, 0, "FTC005",
                f"ILLEGAL cell {cell!r} uses axis values the builder "
                "does not define", str(cell)))
            continue
        # the refusal text is user-facing API: each illegal cell needs
        # its exact-message snapshot (tests name cells '(s x d x e)')
        name = f"({s} x {d} x {e})"
        if name not in test_src:
            out.append(_finding(
                test_path, 0, "FTC005",
                f"illegal cell {name} has no refusal-message snapshot "
                "in tests/test_round_builder.py", name))
    if "iter_cells" not in test_src:
        out.append(_finding(
            test_path, 0, "FTC005",
            "the matrix test no longer enumerates iter_cells() — a "
            "new axis value could be silently absent from coverage",
            "iter_cells"))
    return out


def check_builder_cells(root: str) -> List[Finding]:
    rp = _read(root, "fedtorch_tpu/parallel/round_program.py")
    test = _read(root, "tests/test_round_builder.py")
    return diff_builder_cells(axis_tuples(rp), illegal_cells(test), test)


# -- FTC006: lint-rule docs drift ----------------------------------------

_RULE_ID_RE = re.compile(r"`([A-Z]{3}\d{3})`")


def documented_rule_ids(doc_text: str) -> Set[str]:
    """Backticked rule ids appearing anywhere in the doc (the pinned
    markdown_table renders each id as `FTXnnn`)."""
    return set(_RULE_ID_RE.findall(doc_text))


def diff_rule_docs(rule_ids: Iterable[str],
                   documented: Set[str]) -> List[Finding]:
    out: List[Finding] = []
    for rid in sorted(set(rule_ids) - documented):
        out.append(_finding(
            "docs/static_analysis.md", 0, "FTC006",
            f"rule {rid} is registered in lint/rules.py but absent "
            "from the docs/static_analysis.md rule tables", rid))
    return out


def check_rule_docs(root: str) -> List[Finding]:
    """FTH (and the table-rendered FTP/FTC) ids must appear in
    docs/static_analysis.md. FTL ids are documented as unbackticked
    section headings, so only the table-pinned families are diffed."""
    from fedtorch_tpu.lint.rules import (
        CONCURRENCY_RULES, PROGRAM_RULES, REGISTRY_RULES,
    )
    doc = _read(root, "docs/static_analysis.md")
    ids = (list(CONCURRENCY_RULES) + list(PROGRAM_RULES)
           + list(REGISTRY_RULES))
    return diff_rule_docs(ids, documented_rule_ids(doc))


# -- the whole registry audit --------------------------------------------

def audit_registries(root: str) -> List[Finding]:
    """All FTC checks over a repo checkout; sorted findings."""
    obs = _read(root, "docs/observability.md")
    findings: List[Finding] = []
    findings += diff_metric_fields(
        emitted_row_fields(root), cataloged_row_fields(),
        documented_row_fields(obs))
    findings += diff_event_names(
        emitted_event_names(root), documented_event_names(obs))
    findings += check_seams(root)
    findings += check_config_cli(root)
    findings += check_builder_cells(root)
    findings += check_rule_docs(root)
    return sorted(findings, key=lambda f: (f.rule, f.path, f.message))
