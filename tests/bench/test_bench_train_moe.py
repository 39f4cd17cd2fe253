"""The ``train_moe`` driver end to end on a tiny copy of
``train-moonlight-ep8-8k``, against its plain reference.

The copy keeps the cell's files and changes only widths, depth, expert and
vocabulary counts and lengths: 1 dense + 2 MoE layers, experts 4-7 of 16
held, seq 64, batch 2.  Nothing measured here is a device number."""
import copy
import os
import time

import _tiny_cells
import pytest

from bench import manifest, moe_work
from bench.run import Env, execute

TPU_TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")

WORKLOAD = "train-moonlight-ep8-8k"
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=32, experts_routed=16, n_routed_experts=4,
            experts_held_first=4, vocab_size=256)
TINY_PROGRAM = dict(d_model=64, d_ff=128, num_heads=4, num_kv_heads=4,
                    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, moe=dict(num_experts=16, d_ff_expert=32))


def _cell(**program) -> manifest.Cell:
    real = manifest.load_cell(WORKLOAD)
    config = copy.deepcopy(real.config)
    config.update(TINY)
    config["program"]["overrides"].update(TINY_PROGRAM, **program)
    mix = dict(real.traffic, seq=64, batch=2, trace_seconds=1)
    return manifest.Cell(
        name=WORKLOAD, chips=1, config_name=real.config_name, config=config,
        traffic_name=real.traffic_name, traffic=mix, end_to_end=real.end_to_end,
        per_layer=real.per_layer, limits=real.limits, root=_tiny_cells.ROOT,
    )


def test_float32_run_equals_the_reference():
    """Computed in float32 like the reference, the program's three steps
    (latent attention, dropless held experts, balance loss, bias update)
    agree with it to float32 rounding: each gap under 1e-5, no bias entry
    apart."""
    records = {}
    out = execute(_cell(compute_dtype="float32"), _tiny_cells.env(
        seconds=1.0, hooks={"run_record": lambda r: records.update(run=r)}))
    assert out["correct"], out["compared"]
    gaps = {k: v["value"] for k, v in out["compared"].items()}
    assert gaps.pop("bias_gap") == 0.0
    assert max(gaps.values()) < 1e-5, gaps
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert out["device"]["memory_compiled_bytes"] > 0
    assert records["run"]["moe"]["held_assignments"] == []  # nothing traced


def test_bias_left_unmoved_fails():
    """A step that keeps the router's correction bias where it was: the
    four gradient gaps cannot see it (the bias takes no gradient), the
    bias gap does."""
    def frozen_bias(step):
        def f(state, batch):
            new, met = step(state, batch)
            moe = dict(new.params["blocks"]["moe"],
                       bias=state.params["blocks"]["moe"]["bias"])
            blocks = dict(new.params["blocks"], moe=moe)
            return new._replace(params=dict(new.params, blocks=blocks)), met
        return f

    out = execute(_cell(compute_dtype="float32"),
                  _tiny_cells.env(seconds=1.0, hooks={"step_fn": frozen_bias}))
    assert not out["correct"]
    gap = out["compared"]["bias_gap"]
    assert gap["value"] > gap["limit"], gap


def test_work_counts_by_hand():
    """The cell's forward work a token, by part, in MFLOP (2 per MAC):
    MLA projections 2*6*(2048*16*192 + 2048*576 + 512*16*256 + 16*128*2048),
    causal scores 2*16*(192+128)*(8193/2)*6, the dense FFN 6*2048*11264,
    the shared experts 5*6*2048*2816, the routers 5*2*2048*64, the head
    2*2048*20480, and the routed experts at 768 assignments a held expert
    (8 experts, 5 layers) 6144*5*6*2048*1408/8192."""
    m = manifest.load_cell(WORKLOAD).config
    per_tok = {
        "mla": 2 * 6 * moe_work.attn_params(m),
        "scores": 2 * 16 * 320 * 8193 / 2 * 6,
        "dense": 6 * 2048 * 11264,
        "shared": 5 * 6 * 2048 * 2816,
        "router": 5 * 2 * 2048 * 64,
        "head": 2 * 2048 * 20480,
    }
    assert per_tok["mla"] == 2 * 6 * (2048 * 16 * 192 + 2048 * 576
                                      + 512 * 16 * 256 + 16 * 128 * 2048)
    fixed = moe_work.fixed_forward_flops(m, 8192) / 8192
    assert fixed == pytest.approx(sum(per_tok.values()), rel=1e-12)
    routed = 6144 * 5 * moe_work.expert_flops_per_assignment(m) / 8192
    assert routed == pytest.approx(64.88064e6)
    assert moe_work.train_flops_per_token(m, 8192, 6144 * 5) == pytest.approx(
        3 * (fixed + routed))


class _RecordedTrace(Env):
    """A traced window whose trace is a recorded TPU trace: the CPU's own
    profile has no device plane to reduce."""

    def tick(self, elapsed):
        return self.trace and elapsed >= self.seconds - self.trace_seconds

    def end_window(self):
        from jax.profiler import ProfileData

        from bench import xtrace

        return xtrace.reduce_profile(ProfileData.from_file(TPU_TRACE))


def test_traced_window_reads_back_held_assignments():
    """A traced window reads back the traced steps' held assignments, from
    which ``mfu_pct.train`` counts the routed experts' work."""
    records = {}
    env = _RecordedTrace(seed=5, seconds=2.0, trace=True, chips=1,
                         peaks=_tiny_cells.PEAKS, trace_seconds=1.0,
                         hooks={"run_record": lambda r: records.update(run=r)},
                         t_start=time.perf_counter())
    out = execute(_cell(compute_dtype="float32"), env)
    assert out["correct"], out["compared"]
    moe = records["run"]["moe"]
    assert moe["held_assignments"] and all(h >= 0 for h in moe["held_assignments"])
    assert "mfu_pct.train" in out["metrics"]


def test_control_fails_the_limits():
    """The control of ``bench/control_moe.py`` (the reference with float8
    operands) against the float32 reference, compared by
    ``check/train_moe.compare``, comes out not correct under the cell's
    limits."""
    from bench import control_moe, traffic
    from bench.check import train_moe as tcheck
    from bench.check.judge import judge

    seed = 2**31 + 99
    cell = _cell()
    m, fam = cell.config, cell.family()
    batches = [traffic.train_batch(cell.traffic, m["vocab_size"], seed, i)
               for i in range(3)]
    ref = tcheck.reference(fam, m, m["optimizer"], lambda: fam.init(m, seed, "float32"),
                           batches, gamma=m["bias_update_speed"])
    ok, compared = judge(control_moe.control_numbers(cell, seed, ref), cell.limits)
    assert not ok, compared
