"""The control of ``correct``: the plain reference put in the program's
place at the precision below the configuration's, which the comparison
has to find wrong.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it draws the cell's first timed round, continues each
prompt with seeded tokens of the round's output lengths, picks the sample
``bench/correct.py`` would compare, and runs the reference over it three
times: storing bfloat16 (the configuration's dtype: the reference itself),
float8 (the control) and float32 (to show that a precision above the
configuration's disagrees too, through the binarised MLP's sign()).  At
each compared position, the token each run puts first is scored by its
gap below the bfloat16 reference's best logit.  It also checks that the
reference's initialiser draws the program's weights bit for bit.  One
JSON line per seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import correct
import run
import traffic


def program_weights_match(cell: dict, ref_params, key) -> bool:
    """True when the program's initialiser, jitted as the harness calls
    it, gives the reference's weights bit for bit."""
    import functools

    import jax
    from repro.models.api import get_model

    cfg = run.program_config(cell["config"], cell["config"]["model"])
    prog = jax.jit(functools.partial(get_model(cfg).init_params, cfg))(key)
    pairs = [(prog["embed"], ref_params["embed"]),
             (prog["lm_head"], ref_params["lm_head"]),
             (prog["final_norm"], ref_params["final_norm"])]
    blk = prog["scan"]["b0"]
    lay = ref_params["layers"]
    for name in ("wq", "wk", "wv", "wo"):
        pairs.append((blk["attn"][name], lay[name]))
    for name in ("gate", "up", "down"):
        pairs.append((blk["mlp"][name], lay[name]))
    pairs += [(blk["ln1"], lay["ln1"]), (blk["ln2"], lay["ln2"])]
    return all(bool((a == b).all()) for a, b in pairs)


def control_seed(cell: dict, seed: int) -> dict:
    """The control's readings on one seed."""
    config, mix = cell["config"], cell["mix"]
    m = config["model"]
    length = run.slot_length(config["engine"], mix)
    ref = correct.load_reference(config["reference"])
    key = run.weight_key(seed)
    params = ref.init_params(m, key)
    same = program_weights_match(cell, params, key)
    rng = np.random.default_rng([seed, 99])
    served = [(p, list(rng.integers(0, m["vocab_size"], n)))
              for p, n in traffic.make_round(mix, m["vocab_size"], seed, 0)]
    served = [served[i] for i in correct.sample(served, seed)]
    seqs, rows = correct.teacher_forced(served)
    out = {"seed": seed, "program_weights_match": same}
    n_rows = traffic.max_output_len(mix)
    base = ref.logits_at(m, params, seqs, rows, "bfloat16", length, n_rows)
    for storage in ("float8", "float32"):
        lg = ref.logits_at(m, params, seqs, rows, storage, length, n_rows)
        g = correct.gaps(base, [x.argmax(axis=-1) for x in lg])
        out[storage] = correct.summary(g, config["correct"]["wide_gap"],
                                       correct.spread(base))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(run.COMPILE_CACHE))
    cell = run.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload,
                          **control_seed(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
