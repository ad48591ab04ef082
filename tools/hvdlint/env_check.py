"""Checker 2: HVD_TPU_* environment-variable coverage and defaults.

Every ``HVD_TPU_*`` read in Python or C++ is a public configuration
surface; ``docs/running.md`` is its canonical registry.  Three rules:

1. **coverage** — every env var the code reads must appear in
   docs/running.md (table or prose).  A reference's ``HOROVOD_<x>`` row
   also documents the winning ``HVD_TPU_<x>`` spelling, matching the
   aliasing in common/config.py.
2. **no stale rows** — every ``HVD_TPU_*`` name in the running.md table
   must be read somewhere, or the row documents a knob that no longer
   exists.
3. **default agreement** — the defaults must agree across planes
   (engine/cc/engine.h EngineOptions vs common/config.py Config: the C++
   default is what a caller bypassing Python init gets, so divergence is
   a live trap) and between the doc table's numeric default column and
   the dataclass default the code uses.

Dynamic reads through a prefix helper (serving/scheduler.py's
``_int("MAX_BATCH", ...)`` against ``f"HVD_TPU_SERVE_{name}"``) are
resolved by pairing the f-string prefix with the helper's literal first
arguments — new dynamic read sites must follow that idiom to stay
lintable (docs/contributing.md).
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Set, Tuple

from tools.hvdlint import (Violation, iter_py_files, read,
                           strip_cxx_comments, strip_py_comments)

RUNNING_MD = os.path.join("docs", "running.md")
CONFIG_PY = os.path.join("horovod_tpu", "common", "config.py")
ENGINE_H = os.path.join("horovod_tpu", "engine", "cc", "engine.h")
SCHEDULER_PY = os.path.join("horovod_tpu", "serving", "scheduler.py")
CC_DIR = os.path.join("horovod_tpu", "engine", "cc")
# Python trees whose env reads form the public surface (tests excluded:
# their HVD_TPU_TEST_* knobs configure the harness, not the framework).
PY_SCOPE = ["horovod_tpu", "tools"]

_READ_PATTERNS = (
    r"os\.environ\.get\(\s*\"(HVD_TPU_\w+)\"",
    r"os\.environ\[\s*\"(HVD_TPU_\w+)\"\s*\](?!\s*=[^=])",
    r"os\.getenv\(\s*\"(HVD_TPU_\w+)\"",
    r"os\.environ\.setdefault\(\s*\"(HVD_TPU_\w+)\"",
    r"_get\(\s*\"(HVD_TPU_\w+)\"",  # config.py new/old alias helper
    r"_env_int\(\s*\"(HVD_TPU_\w+)\"",  # basics.py endpoint-port helper
)
_DYNAMIC_PREFIX = re.compile(r"os\.environ\.get\(\s*f\"(HVD_TPU_\w+?)_\{")
_HELPER_DEF = re.compile(r"^([ \t]*)def (_\w+)\(", re.M)

# Plane-agreement map: Config field -> EngineOptions field.  Both sides
# are parsed textually so the check needs no imports (and works against
# the synthetic fixtures in tests/test_hvdlint.py).
PLANE_FIELDS = {
    "fusion_threshold": "fusion_threshold",
    "cycle_time_ms": "cycle_time_ms",
    "stall_warning_sec": "stall_warning_sec",
    "collective_timeout_sec": "collective_timeout_sec",
    "cache_capacity": "cache_capacity",
    "autotune_warmup": "autotune_warmup",
    "autotune_window": "autotune_window",
    "compression_min_bytes": "compression_min_bytes",
    "cross_algo_threshold": "cross_algo_threshold",
    "min_np": "min_size",
    # Control-plane topology / steady state (PR 13): a Python/C++ default
    # split here silently changes which protocol a bare-C++ caller runs.
    "coord_tree": "coord_tree",
    "steady_threshold": "steady_threshold",
    "steady_max_period": "steady_max_period",
}

# Doc-table default column -> dataclass default.  ("config", f) reads
# Config in common/config.py; ("serve", f) reads ServeConfig in
# serving/scheduler.py.
DOC_DEFAULTS: Dict[str, Tuple[str, str]] = {
    "HVD_TPU_FUSION_THRESHOLD": ("config", "fusion_threshold"),
    "HOROVOD_FUSION_THRESHOLD": ("config", "fusion_threshold"),
    "HVD_TPU_CYCLE_TIME_MS": ("config", "cycle_time_ms"),
    "HVD_TPU_STALL_WARNING_SEC": ("config", "stall_warning_sec"),
    "HVD_TPU_CACHE_CAPACITY": ("config", "cache_capacity"),
    "HVD_TPU_AUTOTUNE_WINDOW": ("config", "autotune_window"),
    "HVD_TPU_AUTOTUNE_WARMUP": ("config", "autotune_warmup"),
    "HVD_TPU_COMPRESSION_MIN_BYTES": ("config", "compression_min_bytes"),
    "HVD_TPU_CROSS_ALGO_THRESHOLD": ("config", "cross_algo_threshold"),
    "HVD_TPU_FLIGHT_EVENTS": ("config", "flight_events"),
    "HVD_TPU_MIN_NP": ("config", "min_np"),
    "HVD_TPU_RESTART_EPOCH": ("config", "restart_epoch"),
    "HVD_TPU_STEADY_THRESHOLD": ("config", "steady_threshold"),
    "HVD_TPU_STEADY_MAX_PERIOD": ("config", "steady_max_period"),
    "HVD_TPU_ANOMALY_SIGMA": ("config", "anomaly_sigma"),
    "HVD_TPU_ANOMALY_INTERVAL_MS": ("config", "anomaly_interval_ms"),
    # Transport knobs (docs/performance.md#transport).  HVD_TPU_SHM's
    # default is the string "auto" — the numeric comparison skips it, but
    # the entry keeps the registry exhaustive.
    "HVD_TPU_SHM": ("config", "shm"),
    "HVD_TPU_SHM_RING_BYTES": ("config", "shm_ring_bytes"),
    "HVD_TPU_SERVE_PORT": ("serve", "port"),
    "HVD_TPU_SERVE_MAX_BATCH": ("serve", "max_batch"),
    "HVD_TPU_SERVE_PREFILL_CHUNK": ("serve", "prefill_chunk"),
    "HVD_TPU_SERVE_BLOCK_TOKENS": ("serve", "block_tokens"),
    "HVD_TPU_SERVE_KV_BLOCKS": ("serve", "num_blocks"),
    "HVD_TPU_SERVE_MAX_BLOCKS_PER_SEQ": ("serve", "max_blocks_per_seq"),
    "HVD_TPU_SERVE_QUEUE": ("serve", "queue_limit"),
    "HVD_TPU_SERVE_TENANT_INFLIGHT": ("serve", "tenant_max_inflight"),
    "HVD_TPU_SERVE_RING_MIN_TOKENS": ("serve", "ring_min_tokens"),
    "HVD_TPU_SERVE_REQUEST_TIMEOUT_SEC": ("serve", "request_timeout_sec"),
    "HVD_TPU_SERVE_EOS": ("serve", "eos_id"),
    "HVD_TPU_SERVE_IDLE_SLEEP_SEC": ("serve", "idle_sleep_sec"),
}

_NUM_RE = re.compile(r"^-?[\d_]+(\.\d+)?$")
_EXPR_RE = re.compile(r"^[-+*\s().\d_]+$")


def _safe_eval(expr: str,
               names: Dict[str, float]) -> Optional[float]:
    """Evaluate a default expression: arithmetic over numbers,
    already-resolved constant names, and bool literals (Python
    ``True``/``False`` and C++ ``true``/``false`` normalize to 1/0 so
    flag defaults like ``coord_tree`` compare across planes); None for
    anything else (enum values, strings — out of scope for the numeric
    agreement check)."""
    expr = expr.strip()
    expr = re.sub(r"\b[Tt]rue\b", "1", expr)
    expr = re.sub(r"\b[Ff]alse\b", "0", expr)
    for name, value in names.items():
        expr = re.sub(rf"\b{name}\b", repr(value), expr)
    if not expr or not _EXPR_RE.match(expr):
        return None
    try:
        return float(eval(expr, {"__builtins__": {}}, {}))  # noqa: S307
    except Exception:
        return None


def _dynamic_helpers(text: str) -> List[Tuple[str, str]]:
    """(helper name, env prefix) pairs: helper functions whose own BODY
    reads ``os.environ.get(f"HVD_TPU_<prefix>_{...}")``.  Pairing the
    prefix with its enclosing helper — not every helper in the file —
    keeps an unrelated local ``_int()`` (or a second prefix) from
    fabricating phantom env names."""
    defs = list(_HELPER_DEF.finditer(text))
    out = []
    for i, dm in enumerate(defs):
        indent = dm.group(1)
        end = len(text)
        # The body runs until the next def at the same or outer indent.
        for nm in defs[i + 1:]:
            if len(nm.group(1)) <= len(indent):
                end = nm.start()
                break
        pm = _DYNAMIC_PREFIX.search(text, dm.start(), end)
        if pm:
            out.append((dm.group(2), pm.group(1)))
    return out


def collect_env_reads(root: str) -> Dict[str, Tuple[str, int]]:
    """Env var -> (file, line) of one read site, across the Python scope
    and the engine C++ sources."""
    reads: Dict[str, Tuple[str, int]] = {}

    def note(name: str, rel: str, pos_line: int) -> None:
        reads.setdefault(name, (rel, pos_line))

    for rel in iter_py_files(root, PY_SCOPE):
        if rel.startswith(os.path.join("tools", "hvdlint")):
            continue  # the lint's own pattern tables are not reads
        try:
            # Comment-stripped: `# was: os.environ.get("HVD_TPU_X")` is
            # neither a read (false undocumented-var failure) nor keeps
            # a stale doc row alive.
            text = strip_py_comments(read(root, rel))
        except OSError:
            continue
        for pat in _READ_PATTERNS:
            for m in re.finditer(pat, text):
                note(m.group(1), rel, text.count("\n", 0, m.start()) + 1)
        for helper, prefix in _dynamic_helpers(text):
            for hm in re.finditer(
                    rf"\b{helper}\(\s*\"([A-Z0-9_]+)\"", text):
                note(f"{prefix}_{hm.group(1)}", rel,
                     text.count("\n", 0, hm.start()) + 1)
    cc_dir = os.path.join(root, CC_DIR)
    if os.path.isdir(cc_dir):
        for fname in sorted(os.listdir(cc_dir)):
            if not fname.endswith((".cc", ".h")):
                continue
            rel = os.path.join(CC_DIR, fname)
            text = strip_cxx_comments(read(root, rel))
            for m in re.finditer(r"getenv\(\s*\"(HVD_TPU_\w+)\"", text):
                note(m.group(1), rel, text.count("\n", 0, m.start()) + 1)
    return reads


def parse_doc(doc: str) -> Tuple[Set[str], Dict[str, Tuple[str, int]],
                                 Set[str]]:
    """(documented names incl. HOROVOD->HVD_TPU aliases,
    table name -> (default cell, line), table-row names)."""
    documented: Set[str] = set()
    for m in re.finditer(r"\b(HOROVOD|HVD_TPU)_(\w+)", doc):
        documented.add(m.group(0))
        if m.group(1) == "HOROVOD":
            documented.add("HVD_TPU_" + m.group(2))
    defaults: Dict[str, Tuple[str, int]] = {}
    table_names: Set[str] = set()
    for lineno, line in enumerate(doc.splitlines(), 1):
        if not line.startswith("|") or "`" not in line:
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if len(cells) < 3:
            continue
        names = re.findall(r"`((?:HOROVOD|HVD_TPU)_\w+)`", cells[0])
        if not names:
            continue
        table_names.update(n for n in names if n.startswith("HVD_TPU_"))
        cell_defaults = [d.strip() for d in cells[1].split("/")]
        if len(cell_defaults) == len(names):
            pairs = zip(names, cell_defaults)
        else:
            pairs = ((n, cells[1]) for n in names)
        for name, default in pairs:
            defaults[name] = (default, lineno)
            if name.startswith("HOROVOD_"):
                defaults.setdefault("HVD_TPU_" + name[len("HOROVOD_"):],
                                    (default, lineno))
    return documented, defaults, table_names


def parse_dataclass_defaults(text: str,
                             cls: str) -> Dict[str, Optional[float]]:
    """Numeric field defaults of ``class <cls>`` parsed textually; module
    -level ``NAME = <expr>`` constants are resolved first."""
    consts: Dict[str, float] = {}
    for m in re.finditer(r"^([A-Z][A-Z0-9_]*)\s*=\s*([^#\n]+?)\s*(?:#.*)?$",
                         text, flags=re.M):
        val = _safe_eval(m.group(2), consts)
        if val is not None:
            consts[m.group(1)] = val
    cm = re.search(rf"^class {cls}\b.*?:$", text, flags=re.M)
    if not cm:
        return {}
    body = text[cm.end():]
    stop = re.search(r"^\s*@property|^\s*@staticmethod|^\s*def ", body,
                     flags=re.M)
    if stop:
        body = body[:stop.start()]
    fields: Dict[str, Optional[float]] = {}
    for m in re.finditer(
            r"^\s{4}(\w+)\s*:\s*[\w\[\]\". ]+=\s*([^#\n]+?)\s*(?:#.*)?$",
            body, flags=re.M):
        fields[m.group(1)] = _safe_eval(m.group(2), consts)
    return fields


def parse_engine_options(text: str) -> Dict[str, Optional[float]]:
    """Numeric member defaults of EngineOptions in engine.h."""
    text = strip_cxx_comments(text)
    m = re.search(r"struct\s+EngineOptions\s*\{(.*?)\n\};", text,
                  flags=re.S)
    if not m:
        return {}
    fields: Dict[str, Optional[float]] = {}
    for fm in re.finditer(r"^\s*[\w:]+\s+(\w+)\s*=\s*([^;]+);",
                          m.group(1), flags=re.M):
        fields[fm.group(1)] = _safe_eval(fm.group(2), {})
    return fields


def check(root: str) -> List[Violation]:
    out: List[Violation] = []
    try:
        doc = read(root, RUNNING_MD)
    except OSError as exc:
        return [Violation("env", RUNNING_MD, 0,
                          f"cannot read the env-var registry: {exc}")]
    documented, doc_defaults, table_names = parse_doc(doc)
    reads = collect_env_reads(root)
    for name in sorted(reads):
        rel, line = reads[name]
        if name not in documented:
            out.append(Violation(
                "env", rel, line,
                f"{name} is read here but undocumented in "
                f"docs/running.md — every HVD_TPU_* knob needs a row (or "
                f"prose) there"))
    for name in sorted(table_names - set(reads)):
        _, lineno = doc_defaults.get(name, ("", 0))
        out.append(Violation(
            "env", RUNNING_MD, lineno,
            f"{name} is documented but never read by any code in scope: "
            f"stale row, or the read site dropped out of the lintable "
            f"idiom"))

    # Plane default agreement: config.py Config vs engine.h EngineOptions.
    cfg_fields: Dict[str, Optional[float]] = {}
    try:
        cfg_fields = parse_dataclass_defaults(read(root, CONFIG_PY),
                                              "Config")
        eng_fields = parse_engine_options(read(root, ENGINE_H))
    except OSError:
        eng_fields = {}
    if cfg_fields and eng_fields:
        for cfg_name, eng_name in sorted(PLANE_FIELDS.items()):
            c, e = cfg_fields.get(cfg_name), eng_fields.get(eng_name)
            if c is None or e is None:
                continue
            if abs(c - e) > 1e-9:
                out.append(Violation(
                    "env", ENGINE_H, 0,
                    f"default disagreement between planes: "
                    f"Config.{cfg_name}={c:g} (common/config.py) but "
                    f"EngineOptions.{eng_name}={e:g} (engine.h) — a "
                    f"caller bypassing Python init gets different "
                    f"behavior"))

    # Doc-table numeric defaults vs the dataclass defaults the code uses.
    serve_fields: Dict[str, Optional[float]] = {}
    try:
        serve_fields = parse_dataclass_defaults(read(root, SCHEDULER_PY),
                                                "ServeConfig")
    except OSError:
        pass
    for env_name, (src, field) in sorted(DOC_DEFAULTS.items()):
        if env_name not in doc_defaults:
            continue
        cell, lineno = doc_defaults[env_name]
        if not _NUM_RE.match(cell):
            continue  # "off"/"unset"/prose defaults are not comparable
        fields = cfg_fields if src == "config" else serve_fields
        code_val = fields.get(field)
        if code_val is None:
            continue
        if abs(float(cell.replace("_", "")) - code_val) > 1e-9:
            out.append(Violation(
                "env", RUNNING_MD, lineno,
                f"{env_name}: documented default {cell} but the code "
                f"default is {code_val:g} "
                f"({'common/config.py Config.' if src == 'config' else 'serving/scheduler.py ServeConfig.'}"
                f"{field})"))
    return out
