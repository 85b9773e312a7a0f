"""The chunked delta rule against its roofline: the least time the
chip could take for the real chunks of the traced window
(chipbench/counts_gdn_rule.py: a chunk's bytes at the memory's peak or
its operations at the MXU's float32 pace, whichever is longer; the sum
of ``chunks`` on the window's ``serving.prefill_chunk`` spans, in each
delta-rule layer: a group's padding costs time and counts nothing)
over the device time under ``gdn_rule`` in the prefill programs. At the
configuration's sizes the operations bound it, not the bytes (the note
line says which). Layer: model step."""
from chipbench import counts_gdn_rule
from chipbench.metrics import _program_spans as ps
from chipbench.metrics._gdn_scopes import time_by_scope
from chipbench.metrics._util import peak
from chipbench.runners.serve_gdn import layer_mixers

CHUNK_SPAN = "serving.prefill_chunk"


def read(run):
    t = time_by_scope(run, "chunk")
    spans = ps.load(run)
    if t is None or spans is None or run.peaks is None:
        return None
    if t["gdn_rule"] <= 0:
        return None
    chunks = sum(int(s.args["chunks"]) for s in spans.named(CHUNK_SPAN)
                 if "chunks" in s.args)
    if chunks <= 0:
        return None
    cfg = run.config
    layers = layer_mixers(cfg).count("gdn")
    floor_s, bound = counts_gdn_rule.chunk_rule_floor_s(
        rows=int(cfg["program"]["prompt_chunk"]),
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        hbm_bytes_per_s=peak(run, "hbm_bytes_per_s"),
        bf16_flops_per_s=peak(run, "bf16_flops_per_s"))
    print(f"note gdn_rule_roofline chunks {chunks} layers {layers} "
          f"floor_us_a_chunk_and_layer {1e6 * floor_s:.2f} bound {bound} "
          f"gdn_rule_ms {1e3 * t['gdn_rule']:.3f}", flush=True)
    return 100.0 * chunks * layers * floor_s / t["gdn_rule"]
