"""Unit tests for HLO collective parsing + roofline math, and a subprocess
smoke of one real dry-run cell (whisper-tiny, the smallest arch)."""

import json
import pathlib
import subprocess
import sys

import pytest

from conftest import REPO_ROOT, cpu_child_env
from repro.configs import SHAPES, get_config
from repro.launch.roofline import (
    Roofline,
    inner_scan_flops,
    model_flops_for,
    parse_collective_bytes,
)

HLO_SAMPLE = """
HloModule jit_step
  %ag = bf16[16,4096,128]{2,1,0} all-gather(bf16[1,4096,128]{2,1,0} %p), dims={0}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%add
  %rs = bf16[2,512]{1,0} reduce-scatter(bf16[32,512]{1,0} %y), dimensions={0}
  %a2a = bf16[8,64,64]{2,1,0} all-to-all(bf16[8,64,64]{2,1,0} %z), dimensions={0}
  %cp = bf16[4,32]{1,0} collective-permute(bf16[4,32]{1,0} %w), source_target_pairs={{0,1}}
  %ags = (bf16[1,128]{1,0}, bf16[16,128]{1,0}) all-gather-start(bf16[1,128]{1,0} %q)
  %agd = bf16[16,128]{1,0} all-gather-done((bf16[1,128]{1,0}, bf16[16,128]{1,0}) %ags)
  %mm = f32[128,128]{1,0} dot(f32[128,128]{1,0} %a, f32[128,128]{1,0} %b)
"""


class TestParseCollectives:
    def test_kinds_and_bytes(self):
        out = parse_collective_bytes(HLO_SAMPLE)
        assert out["all-gather"] == 16 * 4096 * 128 * 2 + 16 * 128 * 2  # sync + async-done
        assert out["all-reduce"] == 1024 * 4
        assert out["reduce-scatter"] == 2 * 512 * 2
        assert out["all-to-all"] == 8 * 64 * 64 * 2
        assert out["collective-permute"] == 4 * 32 * 2

    def test_async_start_not_double_counted(self):
        out = parse_collective_bytes(HLO_SAMPLE)
        # only the -done result (16*128 bf16) counted for the async pair
        assert out["all-gather"] - 16 * 4096 * 128 * 2 == 16 * 128 * 2

    def test_non_collective_ignored(self):
        out = parse_collective_bytes("%mm = f32[8,8]{1,0} dot(%a, %b)")
        assert out == {}


class TestRooflineMath:
    def test_terms_and_dominant(self):
        rl = Roofline(
            arch="a", shape="s", mesh="single", chips=256,
            hlo_flops=256 * 197e12,          # exactly 1 s of compute
            hlo_bytes=256 * 819e9 * 0.5,     # 0.5 s of memory
            collective_bytes=256 * 4 * 50e9 * 2.0,  # 2 s of collectives
            collectives={}, model_flops=128 * 197e12,
        )
        assert rl.compute_s == pytest.approx(1.0)
        assert rl.memory_s == pytest.approx(0.5)
        assert rl.collective_s == pytest.approx(2.0)
        assert rl.dominant == "collective"
        assert rl.useful_ratio == pytest.approx(0.5)
        assert rl.roofline_fraction == pytest.approx(0.5)

    def test_model_flops_kinds(self):
        cfg = get_config("granite-8b")
        n = cfg.active_param_count()
        tr = model_flops_for(cfg, SHAPES["train_4k"])
        pf = model_flops_for(cfg, SHAPES["prefill_32k"])
        dc = model_flops_for(cfg, SHAPES["decode_32k"])
        assert tr == 6.0 * n * 256 * 4096
        assert pf == 2.0 * n * 32 * 32768
        assert dc == 2.0 * n * 128

    def test_inner_scan_corrections(self):
        assert inner_scan_flops(get_config("granite-8b"), SHAPES["train_4k"]) == 0
        assert inner_scan_flops(get_config("xlstm-350m"), SHAPES["train_4k"]) > 0
        assert inner_scan_flops(get_config("zamba2-2.7b"), SHAPES["train_4k"]) > 0
        assert inner_scan_flops(get_config("xlstm-350m"), SHAPES["decode_32k"]) == 0


@pytest.mark.slow
class TestDryrunCell:
    def test_whisper_decode_cell_compiles(self, tmp_path):
        """One real dry-run cell end to end in a 512-device subprocess."""
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "whisper-tiny", "--shape", "decode_32k",
             "--mesh", "single", "--out", str(tmp_path)],
            capture_output=True, text=True, timeout=900,
            env=cpu_child_env(), cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        rec = json.loads((tmp_path / "dryrun.jsonl").read_text().splitlines()[0])
        assert rec["status"] == "ok"
        assert rec["chips"] == 256
        assert rec["roofline"]["collective_bytes"] > 0
        assert rec["memory"]["peak_bytes_per_device"] < 16 * 2**30
