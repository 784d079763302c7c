"""Operations and bytes of the model's work, from its shapes alone.

These count what the computation needs, whatever implements it: the
binarised MLP counts as its dense GEMM, so a later fused or packed kernel
is read against the same work.  ``m`` is a configuration file's "model"
block.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

BF16 = 2
F32 = 4


def matmul_params(m: dict) -> int:
    """Weights multiplied per token: attention projections and the MLP of
    every layer, plus the LM head (the embedding lookup is no product)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    mlp = (3 if m["mlp_act"] in ("swiglu", "geglu") else 2) * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + d * m["vocab_size"]


def keys_seen(m: dict, positions: np.ndarray) -> np.ndarray:
    """Keys a query at each absolute position attends to (causal, and
    windowed where the model has a window)."""
    seen = np.asarray(positions, np.int64) + 1
    return np.minimum(seen, m["window"]) if m["window"] else seen


def model_flops(m: dict, positions: np.ndarray) -> float:
    """Forward operations for one token fed at each of ``positions``:
    2 per multiply-add of every weight, plus QK^T and PV against the keys
    the token sees."""
    n = len(positions)
    attn = 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] \
        * keys_seen(m, positions).sum()
    return float(2 * matmul_params(m) * n + attn)


def request_positions(prompt_len: int, n_generated: int) -> np.ndarray:
    """Positions of the tokens a request feeds the model: its prompt, then
    every generated token but the last."""
    return np.arange(prompt_len + max(n_generated - 1, 0))


def paged_attention_work(m: dict, poss: np.ndarray, q_lens: np.ndarray,
                         page: int) -> tuple[float, float]:
    """(operations, bytes) of one paged-attention call over a mixed step:
    slot ``s`` carries ``q_lens[s]`` queries from position ``poss[s]``.

    Operations are QK^T and PV against the keys each query sees.  Bytes
    are the least the call must move: the queries in and the output out
    (float32, as the kernel takes and returns them), and the bf16 K and V
    pages that hold the slot's keys, read once."""
    h, kh, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 0.0
    nbytes = 0.0
    for pos, ql in zip(np.asarray(poss), np.asarray(q_lens)):
        if ql <= 0:
            continue
        qpos = np.arange(pos, pos + ql)
        seen = keys_seen(m, qpos)
        flops += 4.0 * h * hd * seen.sum()
        first = int(qpos[0]) + 1 - int(seen[0])     # earliest key any
        last = int(qpos[-1])                        # query of the slot sees
        pages = last // page - first // page + 1
        nbytes += 2.0 * pages * page * kh * hd * BF16 \
            + 2.0 * ql * h * hd * F32
    return flops, nbytes
