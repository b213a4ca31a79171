"""The trace reduction on a small RECORDED device trace: four steps of
``mistral-7b.chat``'s real engine on a TPU v5 lite (my chip run, PR 23;
``tools/record_trace.py``), one of them carrying prefill chunks.  The file
is the profiler's own ``.xplane.pb``, xz-compressed (8.9 MB -> 0.46 MB).
"""
import collections
import lzma
import os

import pytest

from harness import xtrace
from tinytree import HERE

TRACE = os.path.join(HERE, "data", "mistral_chat_4steps.xplane.pb.xz")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with lzma.open(TRACE) as f:
        return ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def reduction(profile):
    return xtrace.reduce(profile)


def test_op_names_drop_the_hlo_text_and_the_instance_number():
    assert xtrace.op_name(
        "%ragged_paged_attention.28 = f32[64,8,2048,128]{3,2,1,0} "
        "custom-call(s32[64]{0} %copy-done.95)") == "ragged_paged_attention"
    assert xtrace.op_name("%fusion.1234 = bf16[8]{0} fusion(%p)") == "fusion"
    assert xtrace.op_name("%copy = bf16[8]{0} copy(%p)") == "copy"


def test_one_device_plane_and_the_benchmarks_own_host_spans(profile):
    ops = xtrace.device_ops(profile)
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 5298
    names = collections.Counter(n for n, _, _ in ops["/device:TPU:0"])
    # one launch of each kernel per layer per step: 16 layers x 4 steps
    assert names["ragged_paged_attention"] == 64
    assert names["rope_qkv_epilogue"] == 64
    steps = xtrace.host_spans(profile, "bench.engine_step")
    assert len(steps) == 4
    assert all(a < b for a, b in steps)
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(steps, steps[1:]))


def test_busy_time_is_a_union(reduction, profile):
    assert reduction["devices"] == 1
    assert reduction["busy_s"] == pytest.approx(0.333599711, rel=1e-9)
    # leaf events only: what the ops sum to is no more than the union
    # plus rounding, so no parent is counted over its children
    assert sum(reduction["op_seconds"].values()) <= reduction["busy_s"]
    assert sum(reduction["op_seconds"].values()) >= 0.99 * reduction["busy_s"]
    steps = xtrace.host_spans(profile, "bench.engine_step")
    span = (steps[-1][1] - steps[0][0]) / 1e9
    assert reduction["busy_s"] < span


def test_kernel_seconds_and_top_ops(reduction):
    attn = xtrace.kernel_seconds(reduction, "ragged_paged_attention")
    assert attn == pytest.approx(0.092653992, rel=1e-6)
    assert xtrace.kernel_seconds(reduction, "rope_qkv_epilogue") == \
        pytest.approx(0.000374367, rel=1e-6)
    assert xtrace.kernel_seconds(reduction, "no_such_kernel") == 0
    top = xtrace.top_ops(reduction, 3)
    assert [n for n, _ in top] == [
        "ragged_paged_attention", "convert_bitcast_fusion", "fusion"]


def test_idle_gaps_have_an_owner(reduction):
    # the engine blocks on each launch, so the device idles inside step()
    assert set(reduction["idle_by_owner"]) <= {
        "inside engine.step()", "between steps"}
    assert reduction["idle_by_owner"]["inside engine.step()"] == \
        pytest.approx(0.009069576, rel=1e-6)
    assert len(reduction["longest_gaps"]) == 10
    gaps = [g for _, g in reduction["longest_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_merge_is_a_union():
    assert xtrace._merge([(0, 5), (3, 8), (10, 12), (11, 11)]) == \
        [[0, 8], [10, 12]]


def test_a_trace_with_no_device_plane_is_refused():
    class Empty:
        planes = []
    with pytest.raises(ValueError):
        xtrace.reduce(Empty())
