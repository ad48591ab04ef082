"""benchmark/trace_reduce.py on a hand-made trace whose answers can be
worked out on paper, on a small recorded trace of this machine's chip
(`recorded_trace.json.gz`: two steps of `pythia410m_dp4_4x2k` as
`trace_reduce.load()` returned them on the v5e host, cut to the first two
chips), and on an `.xplane.pb` written here by the CPU profiler."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"


def hand_made():
    return {"devices": {CHIP: [["fusion.1|fusion|kLoop|f32[8]", 0, 100],
                               ["all-reduce.1|all-reduce||f32[8]", 100, 50],
                               ["fusion.2|fusion|kOutput|f32[8]", 200, 100]]},
            "async": {CHIP: [["all-reduce-start.2|all-reduce-start||", 120,
                              60]]},
            "spans": [["stage_batch", 0, 10], ["dispatch", 140, 70]]}


def test_intervals():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [[0, 3],
                                                                 [5, 9]]
    assert tr.length([[0, 3], [5, 9]]) == 7
    assert tr.subtract([[0, 10], [20, 30]], [[2, 4], [8, 22], [29, 40]]) \
        == [[0, 2], [4, 8], [22, 29]]
    assert tr.subtract([[0, 10]], []) == [[0, 10]]


def test_hand_made_trace():
    trace = hand_made()
    assert tr.window(trace) == (0, 300)
    assert tr.busy(trace) == {CHIP: 250}           # [0,150] and [200,300]
    assert tr.category_time(trace) == {"fusion_other": 100, "collective": 50,
                                       "matmul_conv": 100}
    # collectives cover [100,180]; compute covers [0,100] and [200,300]
    assert tr.exposed_collective(trace) == 80
    assert tr.top_ops(trace, 2) == [["fusion.1|fusion|kLoop|f32[8]", 1e-7],
                                    ["fusion.2|fusion|kOutput|f32[8]", 1e-7]]
    assert tr.idle_gaps(trace) == [["dispatch", 5e-8]]


# Event names as the v5e's profiler printed them (PR 22), cut in the middle.
FUSION = ("%fusion.23 = (f32[1024,50304]{1,0:T(8,128)}, f32[1024,50304]{1,0:"
          "T(8,128)}) fusion(f32[1024,50304]{1,0:T(8,128)} %copy.1800, "
          "f32[]{:T(128)S(6)} %sub.109), kind=kOutput, "
          "calls=%fused_computation.26")
FLASH = ("%attn.48 = (bf16[16,8192,64]{2,1,0:T(8,128)(2,1)}, bf16[16,8192,64]"
         "{2,1,0:T(8,128)(2,1)}) custom-call(s32[2]{0:T(128)S(1)} "
         "%broadcast.94, bf16[16,8192,64]{2,1,0:T(8,128)(2,1)} "
         "%copy-done.157), custom_call_target=\"tpu_custom_call\"")
LOOP = ("%fusion.2124 = s32[1,16,4,128]{3,2,1,0:T(4,128)S(1)} fusion("
        "s32[4,2048]{1,0:T(4,128)} %batch_0_.1), kind=kLoop, "
        "calls=%fused_computation.2767")
COPY = ("%copy-start.271 = (s32[1,16,4,128]{3,1,2,0:T(8,128)}, u32[]{:S(2)}) "
        "copy-start(s32[1,16,4,128]{3,1,2,0:T(8,128)} %copy.1380)")


@pytest.mark.parametrize("text,short,category", [
    (FUSION, "fusion.23|fusion|kOutput|(f32[1024,50304], f32[1024,50304])",
     "matmul_conv"),
    (FLASH, "attn.48|custom-call||(bf16[16,8192,64], bf16[16,8192,64])",
     "custom_call"),
    (LOOP, "fusion.2124|fusion|kLoop|s32[1,16,4,128]", "fusion_other"),
    (COPY, "copy-start.271|copy-start||(s32[1,16,4,128], u32[])",
     "data_movement"),
    ("%all-reduce-done.3 = f32[8]{0} all-reduce-done(f32[8]{0} %x)",
     "all-reduce-done.3|all-reduce-done||f32[8]", "collective"),
    ("wrapped_reduce-window.5", "wrapped_reduce-window.5|reduce-window||",
     "other"),
])
def test_names_and_categories(text, short, category):
    assert tr.short_name(text) == short
    assert tr.categorize(short) == category


def test_recorded_chip_trace():
    trace = tr.load_saved(os.path.join(HERE, "recorded_trace.json.gz"))
    assert sorted(trace["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    start, end = tr.window(trace)
    busy = tr.busy(trace)
    for plane, ns in busy.items():
        assert 0 < ns <= end - start
    shares = tr.category_time(trace)
    assert shares["custom_call"] > 0 and shares["collective"] > 0
    assert 0 <= tr.exposed_collective(trace) <= end - start
    assert {name for name, _ in tr.idle_gaps(trace)} <= set(
        tr.SPAN_NAMES) | {"no_span"}
    with open(os.path.join(HERE, "recorded_trace.expected.json")) as f:
        import json

        expected = json.load(f)
    assert tr.window(trace) == tuple(expected["window"])
    assert busy == expected["busy"]
    assert shares == pytest.approx(expected["category_time"])
    assert tr.exposed_collective(trace) == pytest.approx(
        expected["exposed_collective"])


def test_reads_an_xplane_with_jax_alone(tmp_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("dispatch"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    profile = tr.read_xplane(tr.find_xplane(str(tmp_path)))
    assert "/host:CPU" in tr.summarize(profile)
    spans = tr.load(profile)["spans"]
    assert [name for name, _, _ in spans] == ["dispatch"]
    assert "tensorflow" not in sys.modules
