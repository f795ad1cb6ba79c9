"""What bfloat16 activations alone move, read as the routed comparison reads
a served run: the reference with every activation rounded to bfloat16
("a16") and, for the control's scale, with int8 weights ("w8") serves its
own first token at every position with its own routes, and the float32
reference reads those tokens' gaps. Beside them, the token one past the
reference's own first at its least (what one altered token reads there).
On random tokens, at the configuration's widths; run on the chip by hand
(the benchmark's own runs never run it):

    python3 benchmark/tests/rounding_witness.py \\
        benchmark/configs/command-a-plus-05-2026-serve.json \\
        --length 6144 --rows 2 --seeds 4000003909 [--query-init-scale 2.5]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import lib  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--length", type=int, default=6144)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--query-init-scale", type=float, default=None)
    args = ap.parse_args()
    cfg = lib.read_json(args.config)
    if args.query_init_scale is not None:
        cfg["assumed"]["query_init_scale"] = args.query_init_scale
    ref = lib.load("reference/" + cfg["reference"] + ".py")
    wdtype = cfg["dtype"]["weights"]
    for seed in args.seeds:
        rng = np.random.default_rng(seed % (2 ** 32))
        tokens = rng.integers(3, cfg["vocab_size"], (args.rows, args.length),
                              dtype=np.int32)
        # the second half of each row: past a window of half its length
        positions = np.tile(np.arange(args.length // 2, args.length,
                                      dtype=np.int32), (args.rows, 1))
        zero = np.zeros_like(positions)
        own = ref.served_scores(cfg, seed, wdtype, tokens, positions, zero)
        first, best = np.asarray(own["first"]), np.asarray(own["best"])
        mine = ref.served_scores(cfg, seed, wdtype, tokens, positions, first)
        altered = best - np.asarray(mine["got_next"])
        out = {"seed": seed,
               "query_init_scale": cfg["assumed"].get("query_init_scale", 1),
               "positions": int(first.size),
               "altered_least": float(altered.min())}
        for lowp in ("a16", "w8"):
            stand_in = ref.served_scores(cfg, seed, wdtype, tokens, positions,
                                         zero, lowp=lowp)
            served = np.asarray(stand_in["first"])
            back = ref.served_scores(cfg, seed, wdtype, tokens, positions,
                                     served, routes=np.asarray(stand_in["chosen"]))
            gaps = np.asarray(back["best"]) - np.asarray(back["got"])
            out[lowp] = {"logit_gap_mean": float(gaps.mean()),
                         "logit_gap_max": float(gaps.max()),
                         "routing_margin_gap": float(
                             np.asarray(back["margin"]).max()),
                         "tokens_not_first_pct": 100.0 * float(
                             (served != first).mean())}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
