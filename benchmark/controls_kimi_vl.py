#!/usr/bin/env python3
"""The controls of the Kimi-VL cell: what `correct` has to fail. `float8` is
the nearest precision below the one the configuration states; every other
one is ONE departure planted in the plain reference
(`benchmark/reference/kimi_vl.py`, `m["fault"]`) and judged in the system's
place. `FAILS` says which compared number holds each mechanism: the limits in
`benchmark/traffic/doc_pages_closed.json` lie between the system's readings
and these.

  python3 benchmark/controls_kimi_vl.py --seed 2100000043 [--controls a,b | -]

runs `benchmark/controls.py` (one window, the sound check, then every control
in one process) on this configuration's cell."""
import os
import sys

FAULTS = ("resize_keys", "table_cropped", "no_rope_2d", "rope_2d_swapped",
          "rope_not_interleaved", "scale_one", "softmax_scores",
          "shared_narrow", "media_shifted")
CONTROLS = ("float8",) + FAULTS
FAILS = {
    "float8": ("logit_gap_sigma", "routed_gap", "mla_gap", "tower_gap",
               "tower_attn_gap"),
    "resize_keys": ("table_gap",),        # jax.image.resize: Keys, a = -0.5
    "table_cropped": ("table_gap",),      # the table not resized
    "no_rope_2d": ("tower_attn_gap",),
    "rope_2d_swapped": ("tower_attn_gap",),   # rows and columns swapped
    "rope_not_interleaved": ("mla_gap",),
    "scale_one": ("routed_gap",),         # routed_scaling_factor 1
    "softmax_scores": ("routed_gap",),
    "shared_narrow": ("ffn_gap",),        # the shared MLP 1,408 wide
    "media_shifted": ("splice_gap",),     # media rows one position on
}
WORKLOAD = "kimi_vl_doc_pages_decode"

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import controls

    argv = sys.argv[1:]
    if "--workload" not in argv:
        argv = ["--workload", WORKLOAD] + argv
    controls.main(argv)
