"""The trace reduction on a constructed trace whose numbers are known:
two harness steps on the host, three operations on one TPU device (one
of them the decode kernel's Pallas call, a custom-call inside the
decode module), and idle gaps under a step and
under the harness's bookkeeping."""
import jax
import pytest

from bench.kernel_names import kernel_of
from bench.trace_reduce import _union, reduce_profile

# times in ns from the line's start (offsets are in ps)
TRACE = """
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 1500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.harness" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000
             stats { metadata_id: 1
                     str_value: "jit(decode_fn)/while/body/pallas_call" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 15000000 duration_ps: 2000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%closed_call.3 = custom-call(s32[2]), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_decode_fn" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    return reduce_profile(jax.profiler.ProfileData.from_text_proto(TRACE))


def test_union_merges_overlaps():
    assert _union([(5, 9), (0, 2), (1, 3), (9, 10), (12, 13)]) == [
        (0, 3), (5, 10), (12, 13)]


PALLAS = 'custom_call_target="tpu_custom_call"'


@pytest.mark.parametrize("label,kind", [
    ("jit_decode_fn %closed_call.19 = bf16[16,16,1,128] custom-call(s32[16,32]"
     "), " + PALLAS, "decode"),
    ("jit_chunk_fn %closed_call.8 = (f32[1,16,256,2]) custom-call(s32[1,8]), "
     + PALLAS, "chunk"),
    ("jit_decode_fn %custom-call.27 = s32[16,16,128] custom-call(), "
     'custom_call_target="AllocateBuffer"', None),
    ("jit_decode_fn %custom-call.55 = s8[520,16,64,64] custom-call(s8[130]), "
     'custom_call_target="ConcatBitcast"', None),
    ("jit_decode_fn %while.5 = (s32[]) while(s32[])", None),
    ("%closed_call.19 = custom-call(s32[16,32]), " + PALLAS, None),
    ("fusion.12", None)])
def test_kernel_names(label, kind):
    assert kernel_of(label) == kind


def test_window_and_busy(reduced):
    # steps span [1000, 21000] ns; operations cover [2000, 6000] and
    # [16000, 18000]; the module line is not an operation
    assert reduced.window_s == pytest.approx(20000e-9)
    assert reduced.busy_s == pytest.approx(6000e-9)
    assert reduced.n_devices == 1


def test_kernel_time(reduced):
    assert reduced.kernels == {"decode": [pytest.approx(3000e-9)]}


def test_breakdown(reduced):
    assert [n for n, _ in reduced.device_ops] == [
        "%closed_call.3 = custom-call(s32[2]), "
        'custom_call_target="tpu_custom_call"', "fusion.7", "copy.2"]
    # gaps: [6000, 16000] (midpoint under the harness's bookkeeping,
    # the shortest annotation covering it), [18000, 21000] and
    # [1000, 2000] (inside steps)
    assert reduced.idle_gaps == [("bench.harness", pytest.approx(10000e-9)),
                                 ("bench.step", pytest.approx(3000e-9)),
                                 ("bench.step", pytest.approx(1000e-9))]


def test_no_device_is_an_error():
    host_only = TRACE[:TRACE.index("planes {\n  id: 2")]
    with pytest.raises(ValueError):
        reduce_profile(jax.profiler.ProfileData.from_text_proto(host_only))


def test_excerpt_reduces_alike(tmp_path, reduced):
    from bench.tests.excerpt_trace import excerpt
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(TRACE))
    cut = reduce_profile(jax.profiler.ProfileData.from_text_proto(
        excerpt(str(path), steps=2)))
    assert cut == reduced


def test_recorded_chip_trace():
    """An excerpt of a traced run of OLMo-1B batch decode at 16 slots on
    one TPU v5e (bench/tests/excerpt_trace.py): the decode kernel's calls under
    `jit_decode_fn` and the chunk kernel's under `jit_chunk_fn` are found,
    and the device was busy nearly all of the window."""
    import gzip
    from pathlib import Path
    path = Path(__file__).parent / "data" / "olmo-1b.batch-decode.pbtxt.gz"
    with gzip.open(path, "rt") as fh:
        red = reduce_profile(jax.profiler.ProfileData.from_text_proto(
            fh.read()))
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(2.134200129)
    assert red.busy_s == pytest.approx(2.130047421)
    # one decode step in the excerpt: one kernel call per layer (16), and
    # none of the module's XLA custom-calls
    assert len(red.kernels["decode"]) == 16
    assert sum(red.kernels["decode"]) == pytest.approx(1.896198968)
    assert len(red.kernels["chunk"]) == 11
    assert sum(red.kernels["chunk"]) == pytest.approx(0.020748682)
