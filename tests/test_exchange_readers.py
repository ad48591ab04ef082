"""The readers of the compiled step's table of collectives
(benchmark/layer_metrics/_exchange.py) on a hand-made table and a hand-made
device line: two steps of one chip, one asynchronous pair with a carrier
between, one synchronous `model` all-reduce.  The numbers are made up (no
measurement); every expected value is arithmetic on them."""

import importlib
import types

import pytest

from benchmark import program_trace
from benchmark.layer_metrics import _exchange

TABLE = [
    {"op": "all-reduce", "asynchronous": True,
     "start": "async-collective-start", "done": "async-collective-done",
     "instruction": None, "carriers": ["fusion.2"],
     "carrier_op_names": ["jit(shard_step)/shard_map/hvd_optimizer/add"],
     "bytes": 400_000, "dtype": "bf16", "replica_groups": "{{0,1,2,3}}",
     "op_name": "jit(shard_step)/shard_map/transpose(jvp(hvd_loss))/"
     "hvd_mlp/down/psum_invariant", "role": "gradient"},
    {"op": "all-reduce", "asynchronous": False, "start": None, "done": None,
     "instruction": "all-reduce.7", "carriers": [], "carrier_op_names": [],
     "bytes": 100_000, "dtype": "f32", "replica_groups": "{{0,1,2,3}}",
     "op_name": "jit(shard_step)/shard_map/jvp(hvd_loss)/norm/psum_invariant",
     "role": "model"}]
# One step, from its first operation: (instruction, opcode, kind, start,
# duration) in ns.  The pair is in flight from 100 to 340 and the synchronous
# all-reduce from 340 to 390: 290 ns a step, of which the core waits 90.
STEP = [("fusion.1", "fusion", "kOutput", 0, 100),
        ("async-collective-start", "fusion", "kCustom", 100, 10),
        ("fusion.2", "fusion", "kOutput", 110, 200),
        ("async-collective-done", "fusion", "kCustom", 310, 30),
        ("all-reduce.7", "all-reduce", "", 340, 50),
        ("fusion.3", "fusion", "kLoop", 390, 510)]
PROGRAM = {"devices": {"/device:TPU:0": [
    [f"{name}|{opcode}|{kind}|", begin + start, duration]
    for begin in (0, 1000) for name, opcode, kind, start, duration in STEP]},
    "program_spans": []}
WINDOW_NS = 1900.0
# 8e13 bits a second is 10,000 bytes a nanosecond; a ring over four chips
# sends 1.5 times the bytes; 500,000 bytes a step, two steps, 580 ns in
# flight.
PEAK = {"ici_bits_per_s": 8e13}
WANT = {"exchange_wait_pct": 100.0 * 180 / WINDOW_NS,
        "exchange_model_wait_pct": 100.0 * 100 / WINDOW_NS,
        "exchange_ici_pct": 100.0 * (1.5 * 1_000_000 / 10_000) / 580,
        "exchange_mb": 0.5,
        "exchange_async_pct": 80.0}


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def run_with(probed):
    return {"cell": {"name": "hand_made"}, "chips": 4, "peak": PEAK,
            "probes": {} if probed is None else {_exchange.PROBE: probed}}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(program_trace, "of_run", lambda run: PROGRAM)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_its_number(traced, name):
    got = reader(name).read(run_with({"table": TABLE, "read_s": 0.0}))
    assert got == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_run_without_the_probe_reads_nothing(traced, name):
    """A parent of the table (the probe gave None), a cell that does not
    list the probe's owner, and a one-device step's empty table."""
    assert reader(name).read(run_with(None)) is None
    assert reader(name).read({"cell": {"name": "hand_made"}, "chips": 4,
                              "peak": PEAK,
                              "probes": {_exchange.PROBE: None}}) is None
    assert not reader(name).read(run_with({"table": [], "read_s": 0.0}))


@pytest.mark.parametrize("name", ["exchange_wait_pct",
                                  "exchange_model_wait_pct",
                                  "exchange_ici_pct"])
def test_a_run_without_a_trace_reads_no_time(monkeypatch, name):
    monkeypatch.setattr(program_trace, "of_run", lambda run: None)
    assert reader(name).read(run_with({"table": TABLE,
                                       "read_s": 0.0})) is None


def test_an_interconnect_share_needs_a_peak_and_several_chips(traced):
    probed = {"table": TABLE, "read_s": 0.0}
    assert _exchange.ici_pct(dict(run_with(probed), peak=None)) is None
    assert _exchange.ici_pct(dict(run_with(probed), chips=1)) is None


def test_a_done_is_paired_with_the_start_before_it():
    """A trace that opens inside a step (a done whose start it missed)
    pairs every start with the first done that begins after it."""
    seen = {"async-collective-start": [(100, 10), (1100, 10)],
            "async-collective-done": [(40, 30), (310, 30), (1310, 30)]}
    assert _exchange._flights(TABLE[0], seen) == [(100, 340), (1100, 1340)]
    assert _exchange._flights(TABLE[1], {"all-reduce.7": [(340, 50)]}) \
        == [(340, 390)]


def test_the_breakdown_says_where_a_steps_exchange_time_goes():
    got = _exchange.breakdown(TABLE, PROGRAM)
    assert got["steps"] == 2 and got["step_ms"] == pytest.approx(1000 / 1e6)
    assert got["ms_in_starts"] == pytest.approx(10 / 1e6)
    assert got["ms_in_dones"] == pytest.approx(30 / 1e6)
    assert got["ms_in_synchronous"] == pytest.approx(50 / 1e6)
    assert got["ms_in_flight"] == pytest.approx(290 / 1e6)
    assert got["roles"]["gradient"] == {
        "entries": 1, "ms": pytest.approx(40 / 1e6), "mb": 0.4}
    assert got["roles"]["model"] == {
        "entries": 1, "ms": pytest.approx(50 / 1e6), "mb": 0.1}
    assert got["carriers_by_kind_and_phase"] == {"kOutput optimizer": {
        "fusions": 1, "ms": pytest.approx(200 / 1e6)}}
    (ms, op_name, nbytes), = got["longest_dones_ms_opname_bytes"]
    assert ms == pytest.approx(30 / 1e6) and nbytes == 400_000
    assert op_name.endswith("hvd_mlp/down/psum_invariant")
    assert got["first_start_ms_into_step"] == pytest.approx(100 / 1e6)
    assert got["last_done_end_ms_into_step"] == pytest.approx(390 / 1e6)
    assert got["last_flash_bwd_end_ms_into_step"] is None
    assert _exchange.breakdown([], PROGRAM) is None
    assert _exchange.breakdown(TABLE, None) is None


def test_the_probe_copies_the_table_of_the_step_that_ran(monkeypatch,
                                                         tmp_path):
    """The probe takes `step.collectives()` as it is after the window and
    notes the breakdown; a step without the table (a parent of it) and one
    that holds no executable (the jit's own call) give None."""
    monkeypatch.setattr(program_trace, "HERE", str(tmp_path))   # no trace
    notes = []

    def context(step):
        return {"built": types.SimpleNamespace(step=step),
                "note": lambda **fields: notes.append(fields)}

    probed = _exchange.probe(context(
        types.SimpleNamespace(collectives=lambda: TABLE)))
    assert probed["table"] == TABLE and probed["table"][0] is not TABLE[0]
    assert probed["read_s"] >= 0.0
    assert notes[0]["exchange_probe"]["entries"] == 2
    assert _exchange.probe(context(types.SimpleNamespace())) is None

    def holds_nothing():
        raise ValueError("this step holds no executable of its own")

    assert _exchange.probe(context(
        types.SimpleNamespace(collectives=holds_nothing))) is None
