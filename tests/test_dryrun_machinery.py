"""Dry-run machinery tests that run fast on 1 device:
roofline parsing, shape specs, step builders at reduced scale."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, all_cells, get_reduced_config
from repro.launch import roofline as rl


def test_all_cells_enumeration():
    cells = all_cells()
    # 10 archs x 4 shapes - 1 documented skip (whisper x long_500k)
    assert len(cells) == 39
    assert ("whisper_large_v3", "long_500k") not in cells
    assert ("mamba2_780m", "long_500k") in cells


def test_collective_parser_counts_ring_bytes():
    hlo = """
  %ar = f32[1024,256]{1,0} all-reduce(f32[1024,256] %x), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[512,128]{1,0} all-gather(bf16[32,128] %y), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = f32[64]{0} collective-permute(f32[64] %z), source_target_pairs={{0,1}}
  %aa = f32[8,64]{1,0} all-to-all(f32[8,64] %w), replica_groups=[2,8]<=[16]
"""
    out = rl.collective_wire_bytes(hlo)
    ar_bytes = 1024 * 256 * 4
    assert abs(out["all-reduce"] - 2 * ar_bytes * 15 / 16) < 1
    ag_bytes = 512 * 128 * 2
    assert abs(out["all-gather"] - ag_bytes * 3 / 4) < 1
    assert out["collective-permute"] == 64 * 4
    assert abs(out["all-to-all"] - 8 * 64 * 4 * 7 / 8) < 1
    assert out["counts"]["all-reduce"] == 1


def test_collective_parser_ignores_done_ops():
    hlo = """
  %s = f32[128]{0} all-gather-start(f32[32] %x), replica_groups={{0,1,2,3}}
  %d = f32[128]{0} all-gather-done(f32[128] %s)
"""
    out = rl.collective_wire_bytes(hlo)
    assert out["counts"]["all-gather"] == 1


def test_roofline_dominant_term():
    rep = rl.analyze(
        arch="a", shape_name="s", mesh_name="m", chips=256,
        cost={"flops": 1e15, "bytes accessed": 1e9},
        hlo_text="", memory_stats=None, model_flops=6e17,
    )
    assert rep.dominant == "compute"
    assert abs(rep.compute_s - 1e15 / rl.PEAK_FLOPS) < 1e-9


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "mamba2_780m", "granite_moe_1b_a400m"])
def test_step_builders_lower_on_tiny_mesh(arch):
    """build_step lowers (no compile) for each kind on a 1-device mesh with a
    tiny config -- catches spec/struct mismatches without the 512-dev cost."""
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import build_step

    cfg = get_reduced_config(arch)
    mesh = make_test_mesh((1, 1), ("data", "model"))
    for shape_name in ("train_4k", "decode_32k"):
        shape = SHAPES[shape_name]
        small = type(shape)(shape.name, seq_len=64, global_batch=2, kind=shape.kind)
        step, args, in_sh = build_step(cfg, small, mesh)
        with mesh:
            jax.jit(step, in_shardings=in_sh).lower(*args)


def test_model_flops_shapes():
    cfg = get_reduced_config("qwen3_0_6b")
    t = rl.model_flops_for(cfg, SHAPES["train_4k"])
    p = rl.model_flops_for(cfg, SHAPES["prefill_32k"])
    d = rl.model_flops_for(cfg, SHAPES["decode_32k"])
    assert t == 6 * cfg.param_count() * 4096 * 256
    assert p == 2 * cfg.param_count() * 32768 * 32
    assert d == 2 * cfg.param_count() * 128
