"""The benchmark's yardsticks on the CPU: the det3x16 model module's
operations and bytes, the peaks table, its copies of the scene generator
and quality score, and the trace reduction."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import model_flops
from bench.models import det3x16
from bench.quality import frame_quality
from bench.scenes import fleet_pools, poisson_quantiles
from bench.trace_reduce import reduce_profile, short_op, union

ROOT = Path(__file__).resolve().parents[2]
CAM8 = json.loads((ROOT / "bench/configs/det3x16-96x320-cam8.json").read_text())


@pytest.mark.parametrize("rung,expect", [
    ("two_stage", 17_694_720),        # 48x160, 24x80, 12x40 outputs
    ("one_stage", 17_694_720),
    ("early_exit@0.5", 1_658_880),    # one conv, 24x80 output of 48x160
])
def test_conv_flops_from_the_conv_formula(rung, expect):
    assert det3x16.conv_flops(CAM8, rung) == expect


def test_flops_per_frame_adds_the_one_stage_head():
    # 2 * 12 * 40 cells * 16 channels * 4 outputs
    assert (det3x16.flops_per_frame(CAM8, "one_stage")
            - det3x16.conv_flops(CAM8, "one_stage")) == 61_440
    assert (det3x16.flops_per_frame(CAM8, "two_stage")
            == det3x16.conv_flops(CAM8, "two_stage"))


def test_peaks_table_and_unknown_kind():
    peak = model_flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        model_flops.peaks("cpu")


def test_least_step_is_memory_bound_at_these_widths():
    peak = model_flops.peaks("TPU v5 lite")
    t, bound = model_flops.least_step_seconds(det3x16, CAM8, "two_stage",
                                              peak, 1)
    # 8 frames of 96x320x3 float32 read once already take 3.6 us
    assert bound == "memory"
    assert t >= 8 * 96 * 320 * 3 * 4 / 819e9
    t4, _ = model_flops.least_step_seconds(det3x16, CAM8, "two_stage",
                                           peak, 4)
    assert t4 == pytest.approx(t / 4)


def test_scene_pool_checksum_is_pinned():
    mix = [["city", 0.0], ["residential", 25.0], ["road", 120.0]]
    pools = fleet_pools(2 ** 31 + 12345, 3, mix, 4, (96, 320, 3))
    h = hashlib.sha256()
    for pool in pools:
        for s in pool:
            h.update(s.image.tobytes())
            h.update(s.boxes.tobytes())
    assert h.hexdigest() == (
        "6fa1cdb4991c001a82c821e585116a39c5e8d301248d02dfc9f03e3220cc48b7")


def test_every_seed_draws_the_same_object_counts():
    a = fleet_pools(1, 2, [["city", 0.0]], 16, (96, 320, 3))
    b = fleet_pools(2 ** 33 + 7, 2, [["city", 0.0]], 16, (96, 320, 3))
    counts = [sorted(len(s.boxes) for s in pool) for pool in a]
    assert counts == [sorted(len(s.boxes) for s in pool) for pool in b]
    assert counts[0] == sorted(poisson_quantiles(12.0, 16).tolist())


KITTI = (375, 1242, 3)


def test_scene_pool_renders_at_a_kitti_frame():
    """A pool at KITTI's 375x1242x3: frames of that shape, every box inside
    the frame and scaled from the base draws, the same object counts for
    every seed, and the same pool again for the same seed."""
    mix = [["city", 60.0], ["road", 0.0]]
    a = fleet_pools(2 ** 31 + 7, 2, mix, 3, KITTI)
    again = fleet_pools(2 ** 31 + 7, 2, mix, 3, KITTI)
    b = fleet_pools(2 ** 33 + 1, 2, mix, 3, KITTI)
    sy, sx = 375 / 96, 1242 / 320
    for pool in a:
        for s in pool:
            assert s.image.shape == KITTI and s.image.dtype == np.float32
            assert 0.0 <= s.image.min() and s.image.max() <= 1.0
            y0, x0, y1, x1 = s.boxes.T
            assert (y0 >= 375 // 3).all() and (x0 >= 0).all()
            assert (y1 <= 375).all() and (x1 <= 1242).all()
            assert ((y1 - y0 >= round(8 * sy))
                    & (y1 - y0 <= round(27 * sy))).all()
            assert ((x1 - x0 >= round(8 * sx))
                    & (x1 - x0 <= round(35 * sx))).all()
    assert ([[len(s.boxes) for s in pool] for pool in a]
            == [[len(s.boxes) for s in pool] for pool in again])
    assert ([sorted(len(s.boxes) for s in pool) for pool in a]
            == [sorted(len(s.boxes) for s in pool) for pool in b])
    for pa, pb in zip(a, again):
        for sa, sb in zip(pa, pb):
            assert sa.image.tobytes() == sb.image.tobytes()
            assert sa.boxes.tobytes() == sb.boxes.tobytes()


def test_scene_copy_draws_like_the_program_generator():
    from repro.perception.data import H, SCENARIOS, W

    from bench import scenes
    assert (scenes.H, scenes.W) == (H, W)
    assert scenes.SCENARIOS == SCENARIOS


def test_quality_copy_matches_the_program_score():
    from repro.anytime.ladder import frame_quality as program_quality
    from repro.perception.data import Scene
    from repro.perception.pipelines import FrameOutput

    rng = np.random.default_rng(0)
    for n_gt, n_det in [(0, 3), (4, 0), (5, 7), (12, 12)]:
        gt = rng.uniform(0, 80, (n_gt, 4)).astype(np.float32)
        gt[:, 2:] = gt[:, :2] + rng.uniform(8, 30, (n_gt, 2))
        det = gt[rng.integers(0, max(n_gt, 1), n_det)] if n_gt else (
            rng.uniform(0, 80, (n_det, 4)).astype(np.float32))
        det = det + rng.normal(0, 3, det.shape).astype(np.float32)
        scene = Scene(image=None, boxes=gt, lane_pixels=None,
                      scenario="city", rain=0.0)
        out = FrameOutput(boxes=det, num_objects=0.0, num_proposals=0.0)
        assert frame_quality(gt, det) == program_quality(scene, out)


def test_short_op_names():
    assert short_op(
        "%fusion.15 = bf16[8,48,160,16]{3,0,2,1:T(8,128)(2,1)S(1)} fusion("
        "bf16[8,96,320,3]{0,3,2,1:T(4,128)(2,1)S(1)} %copy.4)"
    ) == "fusion.15 fusion bf16[8,48,160,16]"
    assert short_op("%while.12 = (s32[]{:T(128)}, pred[8,30]{0,1:T(8,128)}) "
                    "while((s32[]") == "while.12 while tuple"
    assert short_op("jit_counted_step(12)") == "jit_counted_step(12)"


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


SYNTHETIC = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.tick" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 20000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 15000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_counted_step(7)" } }
}
'''


def test_reduce_synthetic_trace_exactly():
    from jax.profiler import ProfileData

    s = reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    # window 100 us; ops [20, 35] us and [90, 100] us once clipped
    assert s.devices == 1
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(25e-6)
    assert s.idle_pct == pytest.approx(75.0)
    assert s.module_s == pytest.approx({"jit_counted_step": 15e-6})
    assert s.op_s == pytest.approx({"fusion.1": 30e-6,
                                    "convolution.2": 10e-6})
    # the gap [0, 20] us has its midpoint inside the tick, [35, 90] us
    # inside the wait
    assert s.idle_gaps == pytest.approx({"bench.tick": 20e-6,
                                         "bench.wait": 55e-6})


def test_reduce_recorded_chip_trace():
    """A 20 ms traced slice of cam8-road-floor recorded on a TPU v5 lite."""
    import gzip

    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (Path(__file__).parent / "data/road_floor.xplane.pb.gz").read_bytes())
    s = reduce_profile(ProfileData.from_serialized_xspace(raw))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.0213417, rel=1e-4)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_pct == pytest.approx(100 * (1 - s.busy_s / s.window_s))
    # the rung step and the all-slots-dirty pack are the only modules
    assert set(s.module_s) == {"jit_counted_step", "jit_counted_pack"}
    assert s.module_s["jit_counted_step"] <= s.busy_s
    # idle time falls inside the harness's own spans, here the engine call
    assert sum(s.idle_gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert set(s.idle_gaps) == {"bench.step.early_exit@0.5"}
    assert any(short_op(k).startswith("convolution") for k in s.op_s)
