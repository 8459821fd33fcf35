"""The trace reduction and the kernel-work arithmetic, on a hand-made trace
and HLO text whose answers are worked out below."""
import pytest

from bench import kernel_work, tracing

NS = 1e-9
# an op event on the TPU carries its compiled HLO instruction
PAIRWISE = ('%pairwise.8 = f32[1024,4096]{1,0:T(8,128)} custom-call('
            'f32[1024,768]{1,0:T(8,128)S(1)} %a, f32[4096,768]{1,0:T(8,128)} '
            '%b), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={f32[1024,768]{1,0}, '
            'f32[4096,768]{1,0}}, frontend_attributes={kernel_metadata={}}')


def _planes():
  host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
      ("bench.window", 1000.0, 10000.0),      # [1000, 11000)
      ("bench.submit", 2000.0, 1000.0),       # [2000, 3000)
      ("bench.epoch", 6000.0, 3500.0),        # [6000, 9500)
      ("unrelated", 0.0, 50000.0)]}]}
  dev0 = {"name": "/device:TPU:0", "lines": [
      {"name": "XLA Modules", "events": [("jit_epoch", 0.0, 99999.0)]},
      {"name": "XLA Ops", "events": [
          ("fusion.1", 500.0, 1000.0),        # clipped to [1000, 1500)
          (PAIRWISE, 1000.0, 1000.0),         # [1000, 2000)
          (PAIRWISE, 3000.0, 1000.0),         # [3000, 4000)
          ("all-gather.2", 3500.0, 1000.0),   # [3500, 4500)
          ("while.3", 8000.0, 4000.0)]}]}     # clipped to [8000, 11000)
  dev1 = {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
      ("all-to-all.1", 2000.0, 2000.0)]}]}    # [2000, 4000)
  sparse = {"name": "/device:TPU:0 SparseCore 0", "lines": [
      {"name": "XLA Ops", "events": [("x", 1000.0, 9000.0)]}]}
  return [host, dev0, dev1, sparse]


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
  red = tracing.reduce_trace(_planes())
  # chip 0: [1000,2000) + [3000,4500) + [8000,11000) = 5500 ns
  # chip 1: [2000,4000) = 2000 ns
  assert red.devices == 2
  assert red.window_s == pytest.approx(10000 * NS)
  assert red.busy_s == pytest.approx((5500 + 2000) / 2 * NS)
  assert red.idle_pct == pytest.approx(100 * (1 - 3750 / 10000))


def test_op_time_by_name_and_stem_clipped_to_the_window():
  red = tracing.reduce_trace(_planes())
  assert red.op_s["pairwise.8"] == pytest.approx(2000 * NS)
  assert red.op_count["pairwise.8"] == 2
  assert red.op_s["fusion.1"] == pytest.approx(500 * NS)
  assert red.op_s["while.3"] == pytest.approx(3000 * NS)
  assert tracing.stem("pairwise.8") == "pairwise"
  assert tracing.op_name(PAIRWISE) == "pairwise.8"
  assert tracing.op_name("fusion.1") == "fusion.1"
  assert set(red.calls) == {"pairwise.8"}
  assert tracing.stem("all-gather.2") == "all-gather"
  assert red.top_ops(1) == [["while.3", pytest.approx(3000 * NS)]]


def test_collective_time_by_kind():
  red = tracing.reduce_trace(_planes())
  assert red.collective_s == {"all-gather": pytest.approx(1000 * NS),
                              "all-to-all": pytest.approx(2000 * NS)}


def test_idle_gaps_go_to_the_host_span_that_covers_most_of_them():
  red = tracing.reduce_trace(_planes())
  # chip 0 gaps: [2000,3000) all under bench.submit; [4500,8000) is
  # covered by bench.epoch for 2000 of 3500 ns.  chip 1: [1000,2000) under
  # no span; [4000,11000) is covered by bench.epoch for only 3500 of 7000
  # ns (half: enough).  Averaged over 2 chips.
  assert red.gaps == {"bench.submit": pytest.approx(500 * NS),
                      "bench.epoch": pytest.approx((3500 + 7000) / 2 * NS),
                      "host:unannotated": pytest.approx(500 * NS)}


def test_a_gap_mostly_outside_every_span_is_unannotated():
  planes = _planes()
  planes[0]["lines"][0]["events"][2] = ("bench.epoch", 7000.0, 1000.0)
  red = tracing.reduce_trace(planes)
  # chip 0's [4500,8000) gap is covered for 1000 of 3500 ns: unannotated
  assert red.gaps["host:unannotated"] == pytest.approx(
      (3500 + 1000 + 7000) / 2 * NS)


def test_a_trace_without_window_or_device_work_is_refused():
  planes = _planes()
  with pytest.raises(ValueError, match="bench.window"):
    tracing.reduce_trace(planes[1:])
  with pytest.raises(ValueError, match="no device operation"):
    tracing.reduce_trace([planes[0]])


HLO = """
  %pairwise.8 = f32[1024,4096]{1,0:T(8,128)} custom-call(f32[1024,768]{1,0} %a, f32[4096,768]{1,0} %b), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[1024,768]{1,0}, f32[4096,768]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(f)/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused
  ROOT %other.3 = (f32[1,128]{1,0}, s32[1,128]{1,0}) custom-call(%e, %c), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[512,64]{1,0}, bf16[256,64]{1,0}}, metadata={op_name="x"}
"""


def test_custom_calls_read_operand_and_result_shapes():
  calls = tracing.custom_calls(HLO)
  assert set(calls) == {"pairwise.8", "other.3"}
  assert calls["pairwise.8"] == ([("f32", (1024, 768)), ("f32", (4096, 768))],
                                 [("f32", (1024, 4096))])
  assert calls["other.3"] == ([("f32", (512, 64)), ("bf16", (256, 64))],
                              [("f32", (1, 128)), ("s32", (1, 128))])


def test_kernel_work_from_shapes():
  ops, res = tracing.custom_calls(HLO)["pairwise.8"]
  flops, moved = kernel_work.work("pairwise", ops, res)
  assert flops == 2 * 1024 * 4096 * 768
  assert moved == 4 * (1024 * 768 + 4096 * 768 + 1024 * 4096)
  assert kernel_work.work("no_such_kernel", ops, res) is None
  # a batched second operand: (1024, 768) rows against 128 x 128 rows
  flops, _ = kernel_work.work("pairwise", [("f32", (1024, 768)),
                                           ("f32", (128, 128, 768))],
                              [("f32", (128, 1024, 128))])
  assert flops == 2 * 1024 * 128 * 128 * 768


def test_roofline_share_against_the_peak_table():
  peaks = tracing.load_peaks("TPU v5 lite")
  assert peaks == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
  with pytest.raises(KeyError, match="not in bench/peaks.json"):
    tracing.load_peaks("TPU v9 imaginary")
  red = tracing.reduce_trace(_planes())
  # two calls of 6.44 GFLOP / 32.5 MB: bytes bound, 32505856 / 819e9 s each
  least = 2 * (4 * (1024 * 768 + 4096 * 768 + 1024 * 4096)) / 819e9
  assert tracing.roofline_pct(red, "pairwise", peaks) == \
      pytest.approx(100 * least / (2000 * NS))
  assert tracing.roofline_pct(red, "other", peaks) is None
