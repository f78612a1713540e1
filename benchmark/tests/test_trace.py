"""The trace reduction on a small recorded trace."""

import pytest

from benchmark import trace

# a window of 1000 ns; on the device, ops at [150, 400) and [350, 600)
# (overlapping: busy 450 ns) and [800, 1000); host spans dispatch
# [100, 200), fence [500, 900) — the idle gaps are [0, 150) under
# "bench.dispatch" by its middle (75 ns is before dispatch: window),
# [600, 800) under "bench.fence"
XSPACE = """
planes {
  id: 1
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 400000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.fence" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 150000 duration_ps: 250000 }
    events { metadata_id: 2 offset_ps: 350000 duration_ps: 250000 }
    events { metadata_id: 3 offset_ps: 800000 duration_ps: 200000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 150000 duration_ps: 450000 }
    events { metadata_id: 4 offset_ps: 800000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__search_cache_core(7)" } }
}
planes {
  id: 3
  name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 200000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.9 = f32[8]{0} fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%sort.4 = f32[8]{0} sort()" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_text_proto(XSPACE))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(1000e-9)
    d0 = summary.devices["/device:TPU:0"]
    assert d0.busy_s == pytest.approx(650e-9)  # 450 + 200
    assert summary.devices["/device:TPU:1"].busy_s == pytest.approx(1e-6)
    assert summary.busy_s == pytest.approx((650e-9 + 1e-6) / 2)
    assert summary.idle_share("/device:TPU:0") == pytest.approx(0.35)
    assert summary.idle_share() == pytest.approx(0.175)


def test_op_and_module_time(summary):
    d0 = summary.devices["/device:TPU:0"]
    assert d0.op_s["fusion.1"] == pytest.approx(450e-9)
    assert d0.op_s["all-gather.3"] == pytest.approx(250e-9)
    assert d0.module_s["jit__search_cache_core(7)"] == pytest.approx(650e-9)
    assert d0.module_n["jit__search_cache_core(7)"] == 2
    # device 1: a while of 1000 ns holds fusion.9 (300) which holds
    # sort.4 (100): self times 700, 200, 100; names cut at " = "
    d1 = summary.devices["/device:TPU:1"]
    assert d1.op_s["while.1"] == pytest.approx(700e-9)
    assert d1.op_s["fusion.9"] == pytest.approx(200e-9)
    assert d1.op_s["sort.4"] == pytest.approx(100e-9)
    top = dict(summary.top_ops())
    assert top["while.1"] == pytest.approx(350e-9)  # 700 / 2 devices
    assert top["fusion.1"] == pytest.approx(225e-9)


def test_gap_attribution(summary):
    # device 0: [0, 150) mid 75 -> window; [600, 800) mid 700 -> fence;
    # averaged over the two devices (device 1 has no gap)
    idle = dict(summary.top_gaps())
    assert idle["bench.window"] == pytest.approx(150e-9 / 2)
    assert idle["bench.fence"] == pytest.approx(200e-9 / 2)
    assert sum(idle.values()) == pytest.approx(350e-9 / 2)


def test_no_window_is_an_error():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(
            XSPACE.replace("bench.window", "bench.other")))


@pytest.mark.parametrize("workload", ["tiny-ivfpq.batch",
                                      "tiny-ivfpq.served"])
def test_traced_run_of_each_loop(copy_root, workload):
    """A ``--trace 1`` run of either loop captures, reduces and reports
    (on the CPU the trace holds no TPU plane, so nothing is busy)."""
    from conftest import run_tiny

    out = run_tiny(copy_root, workload, seconds=0.5, trace=True)
    assert out["correct"], out["check"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
