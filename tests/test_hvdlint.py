"""hvdlint: the project-invariant static analysis suite (tools/hvdlint).

Two layers:

* unit tests drive each checker against SMALL SYNTHETIC trees — a wire
  field missing from parse, an undocumented env var, a C symbol without a
  binding, a non-whitelisted lockstep mutation, a bare ``raise
  Exception`` — proving every checker actually rejects its violation
  class (a lint that passes everything would let the contracts drift
  silently);
* tree tests run the suite against THIS repo: clean as shipped (the
  tier-1 wiring — drift fails CI at the PR that introduces it), and
  failing once a real wire parse line or a real docs/running.md env row
  is deleted from a scratch copy (the ISSUE acceptance path).
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.hvdlint import (capi_check, env_check, errors_check,  # noqa: E402
                           lockstep_check, run, wire_check)


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


# ---------------------------------------------------------------------------
# Checker 1: wire-protocol roundtrip (synthetic wire.h / wire.cc).
# ---------------------------------------------------------------------------


_WIRE_H = """
#pragma once
namespace hvdtpu {
struct Request {
  int32_t rank = 0;
  std::string name;
};
struct BitGroup {
  uint32_t slot = 0;
  std::vector<int32_t> ranks;
};
struct RequestList {
  bool shutdown = false;
  std::vector<Request> requests;
  std::vector<BitGroup> bit_groups;
};
struct Response {
  uint8_t type = 0;
};
struct ResponseList {
  bool shutdown = false;
  std::vector<Response> responses;
  bool tuned_present = false;
  int64_t tuned_knob = 0;
  int64_t reshape_knob = 0;
  int64_t reshape_cache_capacity = 0;
  int64_t reshape_compression_min_bytes = 0;
  bool steady_present = false;
  std::vector<uint32_t> steady_pattern;
};
}
"""

_WIRE_CC = """
#include "wire.h"
namespace hvdtpu {
std::vector<uint8_t> SerializeRequestList(const RequestList& rl) {
  w.U8(rl.shutdown); w.U32(rl.requests.size());
  for (const auto& r : rl.requests) { w.I32(r.rank); w.Str(r.name); }
  for (const auto& g : rl.bit_groups) { w.U32(g.slot); w.I32(g.ranks[0]); }
}
bool ParseRequestList(const std::vector<uint8_t>& buf, RequestList* rl) {
  rl->shutdown = rd.U8(); rl->requests.clear();
  { r.rank = rd.I32(); r.name = rd.Str(); }
  rl->bit_groups.clear();
  { g.slot = rd.U32(); g.ranks.push_back(rd.I32()); }
}
std::vector<uint8_t> SerializeResponseList(const ResponseList& rl) {
  w.U8(rl.shutdown);
  for (const auto& r : rl.responses) w.U8(r.type);
  w.U8(rl.tuned_present); w.I64(rl.tuned_knob); w.I64(rl.reshape_knob);
  w.I64(rl.reshape_cache_capacity);
  w.I64(rl.reshape_compression_min_bytes);
  w.U8(rl.steady_present);
  for (uint32_t s : rl.steady_pattern) w.U32(s);
}
bool ParseResponseList(const std::vector<uint8_t>& buf, ResponseList* rl) {
  rl->shutdown = rd.U8();
  { r.type = rd.U8(); rl->responses.push_back(r); }
  rl->tuned_present = rd.U8(); rl->tuned_knob = rd.I64();
  rl->reshape_knob = rd.I64();
  rl->reshape_cache_capacity = rd.I64();
  rl->reshape_compression_min_bytes = rd.I64();
  rl->steady_present = rd.U8();
  { rl->steady_pattern.push_back(rd.U32()); }
}
}
"""


def _wire_tree(tmp_path, header=_WIRE_H, source=_WIRE_CC):
    root = str(tmp_path)
    _write(root, "horovod_tpu/engine/cc/wire.h", header)
    _write(root, "horovod_tpu/engine/cc/wire.cc", source)
    return root


def test_wire_clean_fixture(tmp_path):
    assert wire_check.check(_wire_tree(tmp_path)) == []


def test_wire_field_missing_from_parse(tmp_path):
    source = _WIRE_CC.replace("r.name = rd.Str();", "")
    violations = wire_check.check(_wire_tree(tmp_path, source=source))
    assert any("Request.name" in v.message and "parse" in v.message
               for v in violations), violations


def test_wire_field_missing_from_serialize(tmp_path):
    source = _WIRE_CC.replace("w.Str(r.name);", "")
    violations = wire_check.check(_wire_tree(tmp_path, source=source))
    assert any("Request.name" in v.message and "serialize" in v.message
               for v in violations), violations


def test_wire_steady_field_missing_from_parse(tmp_path):
    """PR-13 satellite: the STEADY broadcast fields are roundtrip-checked
    like every other wire field — a steady_pattern dropped from the parse
    side would silently truncate the pattern and desynchronize the
    self-clocked replay."""
    source = _WIRE_CC.replace("{ rl->steady_pattern.push_back(rd.U32()); }",
                              "")
    violations = wire_check.check(_wire_tree(tmp_path, source=source))
    assert any("ResponseList.steady_pattern" in v.message
               and "parse" in v.message for v in violations), violations


def test_wire_bitgroup_field_missing_from_serialize(tmp_path):
    """PR-13 satellite: the coordinator-tree aggregate's BitGroup rides
    the RequestList codec and its fields are coverage-checked — a
    dropped `ranks` vector would strip the per-rank announce attribution
    the straggler report depends on."""
    source = _WIRE_CC.replace("w.I32(g.ranks[0]);", "")
    violations = wire_check.check(_wire_tree(tmp_path, source=source))
    assert any("BitGroup.ranks" in v.message and "serialize" in v.message
               for v in violations), violations


def test_wire_tuned_knob_without_reshape_counterpart(tmp_path):
    header = _WIRE_H.replace("int64_t reshape_knob = 0;\n", "")
    source = _WIRE_CC.replace("w.I64(rl.reshape_knob);", "").replace(
        "rl->reshape_knob = rd.I64();", "")
    violations = wire_check.check(_wire_tree(tmp_path, header, source))
    assert any("reshape_knob" in v.message and "barrier" in v.message
               for v in violations), violations


# ---------------------------------------------------------------------------
# Checker 2: env-var coverage and defaults (synthetic docs + sources).
# ---------------------------------------------------------------------------


_DOC = """
# running
| Variable | Default | Meaning |
|---|---|---|
| `HVD_TPU_KNOB` | 7 | a documented knob |
"""

_CONFIG = """
DEFAULT_KNOB = 7


class Config:
    knob: int = DEFAULT_KNOB
"""


def _env_tree(tmp_path, doc=_DOC, config=_CONFIG, extra_py=""):
    root = str(tmp_path)
    _write(root, "docs/running.md", doc)
    _write(root, "horovod_tpu/common/config.py",
           config + "\nimport os\nK = os.environ.get(\"HVD_TPU_KNOB\")\n")
    if extra_py:
        _write(root, "horovod_tpu/extra.py", extra_py)
    return root


def test_env_clean_fixture(tmp_path):
    assert env_check.check(_env_tree(tmp_path)) == []


def test_env_undocumented_read(tmp_path):
    root = _env_tree(tmp_path,
                     extra_py="import os\n"
                              "V = os.environ.get(\"HVD_TPU_SECRET\")\n")
    violations = env_check.check(root)
    assert any("HVD_TPU_SECRET" in v.message and "undocumented"
               in v.message for v in violations), violations


def test_env_commented_out_read_is_not_a_read(tmp_path):
    # `# was: os.environ.get("HVD_TPU_OLD")` must neither fail the
    # undocumented-var rule nor keep a stale doc row alive.
    root = _env_tree(
        tmp_path,
        extra_py='X = 1  # was: os.environ.get("HVD_TPU_OLD_KNOB")\n')
    assert env_check.check(root) == []


def test_env_stale_doc_row(tmp_path):
    doc = _DOC + "| `HVD_TPU_GONE` | 1 | removed knob |\n"
    violations = env_check.check(_env_tree(tmp_path, doc=doc))
    assert any("HVD_TPU_GONE" in v.message and "never read" in v.message
               for v in violations), violations


def test_env_doc_default_mismatch(tmp_path):
    # The doc table says 7 but the mapped Config field defaults to 9.
    config = _CONFIG.replace("DEFAULT_KNOB = 7", "DEFAULT_KNOB = 9")
    env_check.DOC_DEFAULTS["HVD_TPU_KNOB"] = ("config", "knob")
    try:
        violations = env_check.check(_env_tree(tmp_path, config=config))
    finally:
        del env_check.DOC_DEFAULTS["HVD_TPU_KNOB"]
    assert any("HVD_TPU_KNOB" in v.message and "documented default 7"
               in v.message for v in violations), violations


def test_env_plane_default_mismatch(tmp_path):
    root = _env_tree(tmp_path, config=_CONFIG.replace(
        "knob: int = DEFAULT_KNOB",
        "knob: int = DEFAULT_KNOB\n    cache_capacity: int = 1024"))
    _write(root, "horovod_tpu/engine/cc/engine.h", """
struct EngineOptions {
  int64_t cache_capacity = 2048;
};
""")
    violations = env_check.check(root)
    assert any("cache_capacity" in v.message and "disagreement"
               in v.message for v in violations), violations


def test_env_dynamic_prefix_resolution(tmp_path):
    # The serving idiom: f"HVD_TPU_SERVE_{name}" + _int("X", ...) resolves
    # to HVD_TPU_SERVE_X, which is undocumented here.
    extra = ("import os\n"
             "def _int(name, default):\n"
             "    return int(os.environ.get(f\"HVD_TPU_SERVE_{name}\")"
             " or default)\n"
             "X = _int(\"WIDGETS\", 3)\n")
    violations = env_check.check(_env_tree(tmp_path, extra_py=extra))
    assert any("HVD_TPU_SERVE_WIDGETS" in v.message
               for v in violations), violations


def test_env_dynamic_prefix_no_cross_product(tmp_path):
    # An unrelated local _int helper (no env read in its body) must not
    # be paired with another helper's prefix — phantom names like
    # HVD_TPU_SERVE_UNRELATED would demand doc rows for knobs that
    # don't exist.
    extra = ("import os\n"
             "def _int(name, default):\n"
             "    return int(os.environ.get(f\"HVD_TPU_SERVE_{name}\")"
             " or default)\n"
             "def _plain(name, default):\n"
             "    return default\n"
             "X = _plain(\"UNRELATED\", 3)\n")
    violations = env_check.check(_env_tree(tmp_path, extra_py=extra))
    assert not any("UNRELATED" in v.message for v in violations), violations


# ---------------------------------------------------------------------------
# Checker 3: C-API parity (synthetic c_api.cc + bindings).
# ---------------------------------------------------------------------------


_C_API = """
extern "C" {
int hvd_tpu_alpha(int a, long long b) { return 0; }
const char* hvd_tpu_beta() { return ""; }
void hvd_tpu_gamma(const char* s) {}
}
"""

_BINDINGS = """
import ctypes
def _load_lib(lib):
    lib.hvd_tpu_alpha.restype = ctypes.c_int
    lib.hvd_tpu_alpha.argtypes = [ctypes.c_int, ctypes.c_longlong]
    lib.hvd_tpu_beta.restype = ctypes.c_char_p
    lib.hvd_tpu_beta.argtypes = []
    lib.hvd_tpu_gamma.restype = None
    lib.hvd_tpu_gamma.argtypes = [ctypes.c_char_p]
"""


def _capi_tree(tmp_path, c_api=_C_API, bindings=_BINDINGS):
    root = str(tmp_path)
    _write(root, "horovod_tpu/engine/cc/c_api.cc", c_api)
    _write(root, "horovod_tpu/common/__init__.py", bindings)
    return root


def test_capi_clean_fixture(tmp_path):
    assert capi_check.check(_capi_tree(tmp_path)) == []


def test_capi_symbol_without_binding(tmp_path):
    c_api = _C_API.replace(
        "void hvd_tpu_gamma(const char* s) {}",
        "void hvd_tpu_gamma(const char* s) {}\n"
        "double hvd_tpu_delta() { return 0; }")
    violations = capi_check.check(_capi_tree(tmp_path, c_api=c_api))
    assert any("hvd_tpu_delta" in v.message for v in violations), violations


def test_capi_argument_count_mismatch(tmp_path):
    bindings = _BINDINGS.replace(
        "lib.hvd_tpu_alpha.argtypes = [ctypes.c_int, ctypes.c_longlong]",
        "lib.hvd_tpu_alpha.argtypes = [ctypes.c_int]")
    violations = capi_check.check(_capi_tree(tmp_path, bindings=bindings))
    assert any("hvd_tpu_alpha" in v.message and "2" in v.message
               for v in violations), violations


def test_capi_argument_type_mismatch(tmp_path):
    # c_int where the C signature takes long long: the top-32-bit
    # truncation class the checker exists for.
    bindings = _BINDINGS.replace(
        "[ctypes.c_int, ctypes.c_longlong]", "[ctypes.c_int, ctypes.c_int]")
    violations = capi_check.check(_capi_tree(tmp_path, bindings=bindings))
    assert any("hvd_tpu_alpha" in v.message and "argtypes[1]" in v.message
               for v in violations), violations


def test_capi_commented_out_binding_does_not_satisfy(tmp_path):
    # A binding commented out during a refactor must read as ABSENT —
    # otherwise the parity check passes while ctypes truncates at
    # runtime.
    bindings = _BINDINGS.replace(
        "    lib.hvd_tpu_alpha.restype = ctypes.c_int",
        "    # lib.hvd_tpu_alpha.restype = ctypes.c_int")
    violations = capi_check.check(_capi_tree(tmp_path, bindings=bindings))
    assert any("hvd_tpu_alpha" in v.message and "restype" in v.message
               for v in violations), violations


def test_capi_reference_to_dead_symbol(tmp_path):
    root = _capi_tree(tmp_path)
    _write(root, "horovod_tpu/user.py", "x = _lib.hvd_tpu_ghost()\n")
    violations = capi_check.check(root)
    assert any("hvd_tpu_ghost" in v.message and "no such symbol"
               in v.message for v in violations), violations


# ---------------------------------------------------------------------------
# Checker 4: lockstep-mutation lint (synthetic engine.cc).
# ---------------------------------------------------------------------------


_ENGINE_GOOD = """
void Engine::ApplyTunedParams(const ResponseList& rl) {
  cur_fusion_.store(rl.tuned_fusion_threshold);
  cache_.Clear();
}
int64_t Engine::SomeReader() {
  return cur_fusion_.load();
}
"""


def _lockstep_tree(tmp_path, engine_cc):
    root = str(tmp_path)
    _write(root, "horovod_tpu/engine/cc/engine.cc", engine_cc)
    return root


def test_lockstep_clean_fixture(tmp_path):
    assert lockstep_check.check(_lockstep_tree(tmp_path,
                                               _ENGINE_GOOD)) == []


def test_lockstep_mutation_outside_whitelist(tmp_path):
    bad = _ENGINE_GOOD + """
void Engine::SneakyApiCall() {
  cur_compression_.store(COMP_BF16);
}
"""
    violations = lockstep_check.check(_lockstep_tree(tmp_path, bad))
    assert len(violations) == 1 and "SneakyApiCall" in violations[0].message


def test_lockstep_free_function_after_whitelisted_member(tmp_path):
    # A static helper defined after a whitelisted member function must
    # not inherit its whitelisting — the exact false-negative shape a
    # review pass caught in this checker's first version.
    bad = _ENGINE_GOOD + """
static void Helper(Engine* e) {
  cur_compression_.store(COMP_BF16);
}
"""
    violations = lockstep_check.check(_lockstep_tree(tmp_path, bad))
    assert len(violations) == 1 and "Helper" in violations[0].message


def test_lockstep_escape_hatch_annotation(tmp_path):
    annotated = _ENGINE_GOOD + """
void Engine::SneakyButJustified() {
  // hvdlint: lockstep-ok(single-rank job; no peer can diverge)
  cur_compression_.store(COMP_BF16);
}
"""
    assert lockstep_check.check(_lockstep_tree(tmp_path, annotated)) == []


# ---------------------------------------------------------------------------
# Checker 5: typed-error discipline (synthetic package).
# ---------------------------------------------------------------------------


def test_errors_bare_exception(tmp_path):
    root = str(tmp_path)
    _write(root, "horovod_tpu/ok.py",
           "def fine():\n"
           "    raise ValueError('typed')\n")
    _write(root, "horovod_tpu/bad.py",
           "def broken():\n"
           "    raise Exception('untyped')\n")
    violations = errors_check.check(root)
    assert len(violations) == 1
    assert violations[0].file.endswith("bad.py")
    assert violations[0].line == 2


# ---------------------------------------------------------------------------
# The real tree: clean as shipped (tier-1 wiring), failing when a real
# invariant is broken in a scratch copy (the ISSUE acceptance path).
# ---------------------------------------------------------------------------


def test_hvdlint_clean_on_this_repo():
    """Tier-1 wiring: `python -m tools.hvdlint` exits 0 on the shipped
    tree, so any wire/env/API/lockstep/error/metric drift fails the suite
    at the PR that introduces it."""
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout, proc.stdout


def test_p2p_plane_is_registered_not_suppressed():
    """The p2p plane extends the checker REGISTRIES (the sanctioned
    path) rather than sprinkling inline suppressions: the sender-side
    residual update is whitelisted by function, and the metrics `p2p`
    section maps to its rendered Prometheus families."""
    from tools.hvdlint.lockstep_check import WHITELIST
    from tools.hvdlint.metrics_check import SECTION_FAMILIES

    assert "Engine::ExecuteSendRecv" in WHITELIST
    assert "p2p" in SECTION_FAMILIES
    assert "hvd_tpu_p2p_transfers_total" in SECTION_FAMILIES["p2p"]
    assert "hvd_tpu_p2p_unmatched" in SECTION_FAMILIES["p2p"]
    # Zero inline escape hatches in the p2p work (the satellite bar).
    cc = os.path.join(REPO, "horovod_tpu", "engine", "cc", "engine.cc")
    with open(cc) as f:
        text = f.read()
    for fn in ("ExecuteSendRecv", "ExecuteGroupAllreduce", "GetP2pChannel"):
        start = text.find(f"Engine::{fn}")
        assert start != -1, fn
        assert "hvdlint: lockstep-ok" not in text[start:start + 4000], fn


def _scratch_copy(tmp_path):
    """Copy the lintable scope of this repo into a scratch root the text
    checkers can be pointed at (binaries and caches skipped)."""
    root = str(tmp_path / "scratch")
    ignore = shutil.ignore_patterns("__pycache__", "*.so", "*.pyc",
                                    ".buildstamp*")
    shutil.copytree(os.path.join(REPO, "horovod_tpu"),
                    os.path.join(root, "horovod_tpu"), ignore=ignore)
    shutil.copytree(os.path.join(REPO, "docs"),
                    os.path.join(root, "docs"), ignore=ignore)
    shutil.copytree(os.path.join(REPO, "tools"),
                    os.path.join(root, "tools"), ignore=ignore)
    return root


_TEXT_CHECKERS = ["wire", "env", "capi", "lockstep", "errors", "model"]


def test_real_tree_copy_is_clean(tmp_path):
    root = _scratch_copy(tmp_path)
    assert run(root, _TEXT_CHECKERS) == []


def test_deleting_a_wire_parse_line_fails(tmp_path):
    root = _scratch_copy(tmp_path)
    wire_cc = os.path.join(root, "horovod_tpu", "engine", "cc", "wire.cc")
    with open(wire_cc) as f:
        text = f.read()
    target = "  rl->abort_message = rd.Str();\n"
    assert target in text
    with open(wire_cc, "w") as f:
        f.write(text.replace(target, ""))
    violations = run(root, ["wire"])
    assert any("abort_message" in v.message for v in violations), violations


def test_deleting_a_doc_env_row_fails(tmp_path):
    root = _scratch_copy(tmp_path)
    doc = os.path.join(root, "docs", "running.md")
    with open(doc) as f:
        lines = f.read().splitlines(keepends=True)
    kept = [l for l in lines if "`HVD_TPU_CACHE_CAPACITY`" not in l]
    assert len(kept) == len(lines) - 1
    with open(doc, "w") as f:
        f.writelines(kept)
    violations = run(root, ["env"])
    assert any("HVD_TPU_CACHE_CAPACITY" in v.message and "undocumented"
               in v.message for v in violations), violations


def test_metrics_checker_honors_foreign_root(tmp_path):
    """A scratch tree's CODE (not just its docs) must be what the
    metrics checker lints: rename a family to camelCase in the copy and
    the checker pointed at the copy flags it, while this repo stays
    clean."""
    root = _scratch_copy(tmp_path)
    metrics_py = os.path.join(root, "horovod_tpu", "common", "metrics.py")
    with open(metrics_py) as f:
        text = f.read()
    assert "hvd_tpu_ops_total" in text
    with open(metrics_py, "w") as f:
        f.write(text.replace("hvd_tpu_ops_total", "hvd_tpu_opsTotal"))
    violations = run(root, ["metrics"])
    assert any("hvd_tpu_opsTotal" in v.message for v in violations), \
        violations
    assert run(REPO, ["metrics"]) == []


def test_cli_reports_file_line_and_exits_1(tmp_path):
    """The CLI contract: violations print as file:line reports on stderr
    and flip the exit code."""
    root = str(tmp_path)
    _write(root, "horovod_tpu/bad.py", "raise Exception('x')\n")
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "errors", "--root", root],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=60)
    assert proc.returncode == 1
    assert "horovod_tpu/bad.py:1" in proc.stderr
    assert "[errors]" in proc.stderr


# ---------------------------------------------------------------------------
# Sanitizer build plumbing (engine/build.py) — no compile, quick tier.
# ---------------------------------------------------------------------------


def test_sanitize_mode_validation(monkeypatch):
    import importlib

    # horovod_tpu.engine re-exports build() the function, which shadows
    # the submodule attribute — resolve the module itself.
    build_mod = importlib.import_module("horovod_tpu.engine.build")
    monkeypatch.delenv("HVD_TPU_SANITIZE", raising=False)
    assert build_mod.sanitize_mode() == ""
    monkeypatch.setenv("HVD_TPU_SANITIZE", "thread")
    assert build_mod.sanitize_mode() == "thread"
    monkeypatch.setenv("HVD_TPU_SANITIZE", "rowhammer")
    with pytest.raises(ValueError):
        build_mod.sanitize_mode()
    # sanitizer_preload must raise the same typed error on an explicit
    # bad mode: the launcher catches ValueError and falls back to the
    # rank-side build() report instead of crashing with a KeyError.
    with pytest.raises(ValueError):
        build_mod.sanitizer_preload("rowhammer")


def test_sanitize_lib_paths_and_flags():
    import importlib

    build_mod = importlib.import_module("horovod_tpu.engine.build")

    assert build_mod.lib_path("").endswith("libhvdtpu.so")
    assert build_mod.lib_path("thread").endswith("libhvdtpu.thread.so")
    assert build_mod.lib_path("address").endswith("libhvdtpu.address.so")
    flags = build_mod._flags("thread")
    assert "-fsanitize=thread" in flags
    assert "-O3" not in flags and "-march=native" not in flags
    normal = build_mod._flags("")
    assert "-O3" in normal and "-fsanitize=thread" not in normal
    # Per-mode stamps: switching modes must never invalidate the normal
    # cached build.
    assert build_mod._stamp_path("thread") != build_mod._stamp_path("")
    assert build_mod._build_stamp("thread") != build_mod._build_stamp("")


# ---------------------------------------------------------------------------
# model: hvdmodel <-> wire.h protocol sync (checker 7).
# ---------------------------------------------------------------------------


def test_model_checker_flags_uncovered_wire_field(tmp_path):
    """Adding a protocol-family field to wire.h without teaching the
    model about it must fail at the introducing PR — the model would
    otherwise keep verifying a stale protocol."""
    root = _scratch_copy(tmp_path)
    wire_h = os.path.join(root, "horovod_tpu", "engine", "cc", "wire.h")
    with open(wire_h) as f:
        text = f.read()
    anchor = "struct ResponseList {\n"
    assert anchor in text
    with open(wire_h, "w") as f:
        f.write(text.replace(anchor,
                             anchor + "  int64_t steady_bogus = 0;\n"))
    violations = run(root, ["model"])
    assert any("steady_bogus" in v.message for v in violations), violations


def test_model_checker_flags_dropped_status_code(tmp_path):
    """The other direction: a StatusCode the C++ still carries may not
    vanish from the model's coverage declaration."""
    root = _scratch_copy(tmp_path)
    cov = os.path.join(root, "tools", "hvdmodel", "coverage.py")
    with open(cov) as f:
        text = f.read()
    assert '"ST_RESHAPE",' in text
    with open(cov, "w") as f:
        f.write(text.replace('"ST_RESHAPE",', ""))
    violations = run(root, ["model"])
    assert any("ST_RESHAPE" in v.message for v in violations), violations


def test_model_checker_flags_unreferenced_coverage_name(tmp_path):
    """A name declared as covered must actually appear in the model
    source — coverage.py cannot drift into aspirational documentation.
    Renaming the model's only references to a field (without touching
    the declaration or the C++) must be flagged."""
    root = _scratch_copy(tmp_path)
    base = os.path.join(root, "tools", "hvdmodel")
    for fname in os.listdir(base):
        if not fname.endswith(".py") or fname == "coverage.py":
            continue
        path = os.path.join(base, fname)
        with open(path) as f:
            text = f.read()
        with open(path, "w") as f:
            f.write(text.replace("dead_ranks", "defunct_ranks"))
    violations = run(root, ["model"])
    assert any("dead_ranks" in v.message for v in violations), violations
