"""The program's spans and scopes in a trace, on the hand-made trace of
``test_bench_tracing.py`` extended with program host spans and scoped ops
(a ``while`` with kernels nested in it); answers worked out below."""
import copy

import pytest

from bench import program_trace as pt
from bench import registry, run, tracing
from bench.test_bench_tracing import PAIRWISE, _planes

NS = 1e-9
EPOCH = "jit(_epoch)/shard_map/"
OP_NAMES = {
    "fusion.1": EPOCH + "greedi.round2/reduce_max:",
    PAIRWISE: "jit(write)/shard_map/store.bound_pass/jit(pairwise)/"
              "pallas_call:",
    "while.3": EPOCH + "greedi.round1/while:",
    # nested in while.3; its outermost scope is round 1
    "fusion.7": EPOCH + "greedi.round1/while/body/greedi.merge/mul:",
    "fusion.8": "jit(write)/shard_map/vmap(store.sieve_scan)/while/body/x:",
    "all-to-all.1": EPOCH + "greedi.partition/all_to_all:",
    # all-gather.2 carries no op_name: unscoped
}


def _extended():
  planes = copy.deepcopy(_planes())
  host = planes[0]
  host["lines"][0]["events"] += [
      ("service.epoch", 6100.0, 3300.0),      # [6100, 9400) in bench.epoch
      ("store.query_call", 3000.0, 100.0)]    # another thread's call
  host["lines"].append({"name": "repro-query-batcher", "events": [
      ("batcher.wait", 1000.0, 800.0),        # [1000, 1800)
      ("batcher.drain", 1900.0, 2500.0),      # [1900, 4400)
      ("store.query_call", 2500.0, 1500.0),   # [2500, 4000)
      ("batcher.drain", 4500.0, 1000.0),      # [4500, 5500)
      ("store.query_call", 4600.0, 800.0),    # [4600, 5400)
      ("PjitFunction(merge_batch)", 2500.0, 10.0)]})
  # kernels inside chip 0's while.3, [8000, 11000) in the window
  planes[1]["lines"][1]["events"] += [("fusion.7", 8200.0, 800.0),
                                      ("fusion.8", 9500.0, 1000.0)]
  return planes


def test_scope_of_takes_the_outermost_program_scope():
  assert pt.scope_of(OP_NAMES["fusion.7"]) == "greedi.round1"
  assert pt.scope_of(OP_NAMES["fusion.8"]) == "store.sieve_scan"
  assert pt.scope_of("jit(f)/probe.outer/while:") is None
  assert pt.scope_of(None) is None


def test_scope_time_is_the_union_of_its_ops_not_the_sum():
  red = pt.reduce_program(_extended(), OP_NAMES)
  assert red.devices == 2
  # round 1 on chip 0: while.3 [8000,11000) holds fusion.7 (and fusion.8,
  # another scope): 3000 ns, where the sum would be 3800
  assert red.scope_s("greedi.round1") == pytest.approx(3000 * NS)
  assert red.scope_s("greedi.round2") == pytest.approx(500 * NS)
  assert red.scope_s("store.bound_pass") == pytest.approx(2000 * NS)
  assert red.scope_s("store.sieve_scan") == pytest.approx(1000 * NS)
  assert red.scope_s("greedi.partition") == pytest.approx(2000 * NS)
  assert red.scope_s("greedi.merge") == 0.0
  # fusion.8 lies inside while.3: the two scopes' union is still 3000
  assert red.scope_s("greedi.round1", "store.sieve_scan") == \
      pytest.approx(3000 * NS)
  # all but all-gather.2's [4000,4500) on chip 0: 7000 of 7500 busy ns
  assert red.busy_s == pytest.approx(3750 * NS)
  assert red.coverage_pct() == pytest.approx(100 * 7000 / 7500)
  assert red.coverage_pct("greedi.partition") == pytest.approx(
      100 * 2000 / 7500)


def test_idle_time_goes_to_the_innermost_open_span():
  red = pt.reduce_program(_extended(), OP_NAMES)
  # chip 0 [2000,3000): bench.submit to 2500 (opened after the drain),
  #   then the drain's store.query_call;
  # chip 0 [4500,8000): drain 100, its call 800, drain 100, none 500,
  #   bench.epoch 100, then service.epoch (nested in it) 1900;
  # chip 1 [1000,2000): batcher.wait 800, none 100, drain 100;
  # chip 1 [4000,11000): drain 400, none 100, the second drain as on chip
  #   0 (1000), none 500, bench.epoch 100 + 100 around service.epoch's
  #   3300, none 1500.  Averaged over 2 chips.
  assert red.gaps == {"bench.submit": pytest.approx(250 * NS),
                      "store.query_call": pytest.approx(1050 * NS),
                      "batcher.drain": pytest.approx(450 * NS),
                      "batcher.wait": pytest.approx(400 * NS),
                      "bench.epoch": pytest.approx(150 * NS),
                      "service.epoch": pytest.approx(2600 * NS),
                      pt.UNANNOTATED: pytest.approx(1350 * NS)}
  assert sum(red.gaps.values()) == pytest.approx(red.window_s - red.busy_s)


def test_only_what_no_span_covers_stays_unannotated():
  planes = _extended()
  batcher = planes[0]["lines"][1]["events"]
  batcher[:] = [e for e in batcher if e[0] != "batcher.wait"]
  red = pt.reduce_program(planes, OP_NAMES)
  assert red.gaps[pt.UNANNOTATED] == pytest.approx((1350 + 400) * NS)
  assert "batcher.wait" not in red.gaps


def test_drain_host_time_leaves_out_its_own_query_calls():
  red = pt.reduce_program(_extended(), OP_NAMES)
  # drains of 2500 and 1000 ns holding calls of 1500 and 800 on their own
  # thread (the python3 thread's call does not count)
  host_ms, call_ms, n = red.drain_split_ms()
  assert n == 2
  assert host_ms == pytest.approx(1e-6 * (1000 + 200) / 2)
  assert call_ms == pytest.approx(1e-6 * (1500 + 800) / 2)


def test_layer_metrics_by_hand():
  red = pt.reduce_program(_extended(), OP_NAMES)
  got = pt.layer_metrics(red, {"epochs": 2, "chunks": 4,
                               "queue_wait_ms": 12.5})
  assert got == {
      # device ms per chip (2 chips) and per epoch (2) or chunk (4)
      "round1_ms.epoch": pytest.approx(1e3 * 3000 * NS / 2 / 2),
      "round2_ms.epoch": pytest.approx(1e3 * 500 * NS / 2 / 2),
      "sieve_scan_ms.ingest": pytest.approx(1e3 * 1000 * NS / 2 / 4),
      "drain_host_ms.query": pytest.approx(1e-6 * 600),
      "queue_wait_ms.query": 12.5}


def test_a_program_without_spans_or_scopes_gives_no_metric():
  red = pt.reduce_program(_planes(), {})
  assert red.scopes() == [] and red.drain_split_ms() is None
  assert pt.layer_metrics(red, {"epochs": 2, "chunks": 4}) == {}
  # bench spans only: chip 0 [2000,3000) bench.submit, [4500,6000) none,
  # [6000,8000) bench.epoch; chip 1 [1000,2000) none, [4000,6000) none,
  # [6000,9500) bench.epoch, [9500,11000) none
  assert red.gaps == {"bench.submit": pytest.approx(500 * NS),
                      "bench.epoch": pytest.approx(2750 * NS),
                      pt.UNANNOTATED: pytest.approx(3000 * NS)}


@pytest.mark.parametrize("metric", sorted(
    {m["name"] for m in registry.load_benchmark()["per_layer"]}))
def test_existing_readers_read_the_same_on_the_extended_trace(metric):
  counters = {"chunks": 4, "drain_ms": 3.0, "occupancy": 5.0,
              "r1_rescans_per_epoch": 7.0}
  peaks = tracing.load_peaks("TPU v5 lite")
  read = registry.Cell(registry.load_benchmark(), "marco-ingest").reader(
      metric)
  before = read(run.Context(tracing.reduce_trace(_planes()), peaks,
                            counters))
  after = read(run.Context(tracing.reduce_trace(_extended()), peaks,
                           counters))
  assert after == before


# ---- the wire format ---------------------------------------------------------

def _varint(n: int) -> bytes:
  out = b""
  while True:
    out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
    n >>= 7
    if not n:
      return out


def _len(field: int, payload: bytes) -> bytes:
  return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field: int, n: int) -> bytes:
  return _varint(field << 3) + _varint(n)


def _plane(name: str, events: list, stats: dict) -> bytes:
  body = _len(2, name.encode())
  for i, (ev, ev_stats) in enumerate(events, 1):
    meta = _int(1, i) + _len(2, ev.encode()) + _len(4, b"short")
    for sid, kind, v in ev_stats:
      meta += _len(5, _int(1, sid) + (_len(5, v.encode()) if kind == "str"
                                      else _int(7, v)))
    body += _len(4, _int(1, i) + _len(2, meta))
  for sid, sname in stats.items():
    body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                         + _len(2, sname.encode())))
  body += _len(3, b"\x08\x01")            # a line: skipped unread
  return _len(1, body)


def test_op_scopes_from_the_xplane_wire_format():
  stats = {7: "tf_op", 8: "hlo_category", 9: "jit(f)/store.write/scatter:"}
  buf = (_plane("/device:TPU:0", [
      ("%fusion.1 = f32[8]{0} fusion()",
       [(8, "str", "loop fusion"), (7, "str", "jit(f)/greedi.round1/a:")]),
      ("%while.3 = (s32[]) while()", [(8, "str", "while")]),
      ("%scatter.2 = f32[8]{0} scatter()", [(7, "ref", 9)])], stats)
      + _plane("/host:CPU", [("service.epoch", [(7, "str", "x")])], stats))
  assert pt.op_scopes_from_bytes(buf) == {
      "%fusion.1 = f32[8]{0} fusion()": "jit(f)/greedi.round1/a:",
      "%scatter.2 = f32[8]{0} scatter()": "jit(f)/store.write/scatter:"}
