"""DeepSeek-V2 serving at a small DeepSeek shape: YaRN rope, group-limited
greedy routing, the expert share, dropless held-expert MoE, and the whole
mixed step against the plain reference ``bench/refs/mla_moe.py``.

The reference imports nothing of the program; it is loaded here by its
path.  Every comparison runs in float32 on the CPU, the Pallas kernel
interpreted.
"""

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels.paged_attention import (VMEM_BUDGET, latent_q_block,
                                           q_row_bytes)
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.models.api import get_model
from repro.models.attention import mla_softmax_scale
from repro.models.layers import rope_tables, yarn_frequencies, yarn_mscale
from repro.runtime import Scheduler, ServeEngine, Telemetry

ROOT = pathlib.Path(__file__).resolve().parents[1]

# DeepSeek-V2's structure at a small width: a dense layer, then MoE
# layers of 16 routed experts in 4 groups (top-2 groups, top-3 experts),
# 2 shared experts, unnormalised gates x 16, YaRN as published
SMALL = dict(num_layers=3, prefix_kinds=("mla_dense",), scan_repeats=2,
             d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
             vocab_size=256, kv_lora_rank=32, q_lora_rank=48,
             rope_head_dim=16, nope_head_dim=16, v_head_dim=16, moe_d_ff=32,
             num_experts=16, n_group=4, topk_group=2, top_k=3,
             experts_held=4, expert_start=4)


def small_cfg(**kw):
    return get_config("deepseek-v2-236b").scaled(**{**SMALL, **kw})


def load_reference():
    path = ROOT / "bench" / "refs" / "mla_moe.py"
    spec = importlib.util.spec_from_file_location("mla_moe_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_block(cfg) -> dict:
    """The configuration file's "model" block of ``cfg``."""
    keys = ("num_layers", "prefix_kinds", "scan_pattern", "scan_repeats",
            "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
            "vocab_size", "num_experts", "num_shared_experts", "top_k",
            "moe_d_ff", "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor", "experts_held", "expert_start",
            "kv_lora_rank", "q_lora_rank", "rope_head_dim",
            "nope_head_dim", "v_head_dim", "mlp_act", "rope_theta",
            "yarn_factor", "yarn_original_max_len", "yarn_beta_fast",
            "yarn_beta_slow", "yarn_mscale", "yarn_mscale_all_dim",
            "norm_eps", "tie_embeddings", "binarize_mlp")
    return {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in ((k, getattr(cfg, k)) for k in keys)}


# ---------------------------------------------------------------------------
# configuration and YaRN
# ---------------------------------------------------------------------------

class TestConfig:
    def test_published_fields(self):
        c = get_config("deepseek-v2-236b")
        assert (c.n_group, c.topk_group, c.top_k, c.num_experts) == \
            (8, 3, 6, 160)
        assert not c.norm_topk_prob and c.routed_scaling_factor == 16.0
        assert (c.yarn_factor, c.yarn_original_max_len) == (40.0, 4096)
        assert c.yarn_mscale == c.yarn_mscale_all_dim == 0.707
        assert c.norm_eps == 1e-6 and c.n_experts_held == 160

    def test_defaults_leave_other_configs_alone(self):
        c = get_config("mixtral-8x22b")
        assert c.n_group == 0 and c.norm_topk_prob and \
            c.routed_scaling_factor == 1.0 and not c.yarn_factor and \
            c.n_experts_held == c.num_experts
        assert rope_tables(c, 64) == (None, 1.0)


class TestYarn:
    def test_frequencies_match_the_published_formulas(self):
        """DeepSeek-V2's YaRN rope at its published settings, written out
        here from the formulas: correction dims floor/ceil of
        d ln(L / (2 pi b)) / (2 ln theta) for b = 32, 1; a linear ramp over
        the pair index between them blends theta^(-2i/d) (kept) into
        theta^(-2i/d) / 40 (interpolated)."""
        d, theta, factor, orig = 64, 10000.0, 40.0, 4096
        lo = math.floor(d * math.log(orig / (32 * 2 * math.pi))
                        / (2 * math.log(theta)))
        hi = math.ceil(d * math.log(orig / (1 * 2 * math.pi))
                       / (2 * math.log(theta)))
        assert (lo, hi) == (10, 23)
        want = []
        for i in range(d // 2):
            base = theta ** (-2 * i / d)
            ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
            want.append(base * (1 - ramp) + base / factor * ramp)
        got = yarn_frequencies(d, theta, factor, orig, 32.0, 1.0)
        np.testing.assert_allclose(got, np.float32(want), rtol=1e-6)
        assert got[0] == 1.0 and got[-1] == pytest.approx(want[-1])

    def test_mscale_and_softmax_scale(self):
        m = 0.1 * 0.707 * math.log(40) + 1
        assert yarn_mscale(40.0, 0.707) == pytest.approx(m)
        assert m == pytest.approx(1.2608, abs=1e-4)
        assert yarn_mscale(1.0, 0.707) == 1.0
        c = get_config("deepseek-v2-236b")
        assert mla_softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m)
        # mscale == mscale_all_dim: cos and sin keep their size
        assert rope_tables(c, 64)[1] == 1.0


# ---------------------------------------------------------------------------
# routing, the expert share, dropless serving
# ---------------------------------------------------------------------------

def _moe_params(cfg, seed=0):
    return moe_mod.moe_init(jax.random.PRNGKey(seed), cfg, jnp.float32)


def _tokens(n, d, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * 0.5


class TestRouting:
    @pytest.mark.parametrize("n_group,topk_group,norm", [
        (4, 2, False), (4, 1, False), (0, 0, True)])
    def test_group_limited_greedy_matches_brute_force(self, n_group,
                                                      topk_group, norm):
        """Every token's experts and gates against an exhaustive numpy
        search: the best ``top_k`` experts among those of the best
        ``topk_group`` groups, a group scored by its best expert."""
        cfg = small_cfg(n_group=n_group, topk_group=topk_group,
                        norm_topk_prob=norm)
        p = _moe_params(cfg)
        x = _tokens(64, cfg.d_model)
        gate, eid, probs = moe_mod.route(p, x, cfg)
        logits = np.asarray(x, np.float64) @ np.asarray(p["router"],
                                                        np.float64)
        sc = np.exp(logits - logits.max(-1, keepdims=True))
        sc /= sc.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(probs), sc, rtol=1e-5)
        e, k = cfg.num_experts, cfg.top_k
        for t in range(len(x)):
            allowed = range(e)
            if n_group:
                size = e // n_group
                best = sorted(range(n_group), key=lambda g:
                              -sc[t, g * size:(g + 1) * size].max())
                allowed = [i for g in best[:topk_group]
                           for i in range(g * size, (g + 1) * size)]
            top = sorted(allowed, key=lambda i: -sc[t, i])[:k]
            assert list(np.asarray(eid[t])) == top
            w = sc[t, top]
            w = w / w.sum() if norm else w * cfg.routed_scaling_factor
            np.testing.assert_allclose(np.asarray(gate[t]), w, rtol=1e-5)


class TestExpertShare:
    def test_shares_add_up_to_the_uncut_layer(self):
        """Eight shares of 2 experts each: every share draws exactly the
        uncut layer's weights of its experts, and the held experts' parts
        of all shares plus the shared experts once give the uncut layer."""
        whole = small_cfg(experts_held=16, expert_start=0)
        pw = _moe_params(whole)
        x = _tokens(24, whole.d_model)[None]
        full, _ = moe_mod.moe_serve(pw, x, whole)
        shared = moe_mod._shared(pw, x[0])
        total = shared
        for i in range(8):
            cfg = whole.scaled(experts_held=2, expert_start=2 * i)
            p = _moe_params(cfg)
            for name in ("w_gate", "w_up", "w_down"):
                assert bool((p[name] == pw[name][2 * i:2 * i + 2]).all())
            assert bool((p["router"] == pw["router"]).all())
            part, _ = moe_mod.moe_serve(p, x, cfg)
            total = total + (part[0] - shared)
        np.testing.assert_allclose(np.asarray(total), np.asarray(full[0]),
                                   rtol=1e-5, atol=1e-6)


class TestDropless:
    def test_every_pair_is_computed(self):
        """A router that sends every token to the same experts: the
        capacity dispatch of training drops tokens, serving drops none
        and equals each token's experts computed one by one."""
        cfg = small_cfg(n_group=0, topk_group=0,
                        experts_held=16, expert_start=0)
        p = _moe_params(cfg)
        p["router"] = p["router"].at[:, :3].add(40.0)     # experts 0-2 win
        x = jnp.abs(_tokens(32, cfg.d_model))[None]
        out, pairs = moe_mod.moe_serve(p, x, cfg)
        assert list(np.asarray(pairs)[:3]) == [32, 32, 32]
        assert int(np.asarray(pairs).sum()) == 32 * 3
        gate, eid, _ = moe_mod.route(p, x[0], cfg)
        want = moe_mod._shared(p, x[0])
        for j in range(cfg.top_k):
            for t in range(32):
                e = int(eid[t, j])
                h = jax.nn.silu(x[0, t] @ p["w_gate"][e]) * \
                    (x[0, t] @ p["w_up"][e])
                want = want.at[t].add(gate[t, j] * (h @ p["w_down"][e]))
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        dropped, _ = moe_mod.moe_apply(p, x, cfg.scaled(
            capacity_factor=1.0))
        assert not np.allclose(np.asarray(dropped), np.asarray(out),
                               atol=1e-3)

    def test_inference_steps_ignore_capacity(self):
        """The model's inference steps route dropless, whatever the
        training capacity: prefill's logits keep their bits when the
        capacity drops tokens, where the training forward's move."""
        cfg = small_cfg(experts_held=0, expert_start=0, dtype="float32")
        api = get_model(cfg)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                  cfg.vocab_size)

        def run(capacity_factor):
            c = cfg.scaled(capacity_factor=capacity_factor)
            full, _ = api.forward(c, params, toks)
            last, _ = api.prefill(c, params, toks, api.init_cache(c, 2, 16))
            return np.asarray(full[:, -1]), np.asarray(last[:, 0])

        full_lo, last_lo = run(0.25)
        full_hi, last_hi = run(8.0)
        np.testing.assert_array_equal(last_lo, last_hi)
        assert not np.allclose(full_lo, full_hi, atol=1e-3)
        np.testing.assert_allclose(last_hi, full_hi, rtol=1e-4, atol=1e-4)

    def test_real_rows_ignore_batch_mates_and_padding(self):
        """A real row's output is the same bits whatever rides beside it:
        other real rows changed, padded rows changed, or none at all."""
        cfg = small_cfg()
        p = _moe_params(cfg)
        x = _tokens(12, cfg.d_model).reshape(3, 4, -1)
        real = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0], [1, 1, 1, 1]], bool)
        base, pairs = moe_mod.moe_serve(p, x, cfg, real)
        noise = _tokens(12, cfg.d_model, seed=9).reshape(3, 4, -1)
        moved, _ = moe_mod.moe_serve(p, jnp.where(real[..., None], x, noise),
                                     cfg, real)
        np.testing.assert_array_equal(np.asarray(moved)[np.asarray(real)],
                                      np.asarray(base)[np.asarray(real)])
        for b, i in zip(*np.nonzero(np.asarray(real))):
            # the row alone real, every other row padding of other values
            only = jnp.zeros_like(real).at[b, i].set(True)
            alone, _ = moe_mod.moe_serve(
                p, jnp.where(only[..., None], x, noise), cfg, only)
            np.testing.assert_array_equal(np.asarray(alone[b, i]),
                                          np.asarray(base[b, i]))
        # padding is routed nowhere
        _, eid, _ = moe_mod.route(p, x, cfg)
        local = np.asarray(eid) - cfg.expert_start
        held = (local >= 0) & (local < cfg.experts_held)
        assert int(np.asarray(pairs).sum()) == \
            int(held[np.asarray(real)].sum())

    @pytest.mark.parametrize("case", ["share", "padding", "idle_expert",
                                      "lanes", "mixtral"])
    def test_scan_reads_each_layers_experts_in_place(self, case):
        """Three scanned MoE layers whose grouped products read the
        layer's experts from the whole stack give, bit for bit, the
        output and per-layer loads of a loop that applies each layer with
        its own sliced weights: with other chips' experts routed to
        (``expert_start`` 4), padding rows, a held expert idle in a layer,
        the gathered backend's vmapped lanes, and a Mixtral block holding
        every expert.

        Both run op by op, and each expert's column of each projection
        has one nonzero of +-1: a product then reads one weight, and its
        bits do not hang on how the CPU's grouped product (a dense
        contraction over every group) orders its sums, while an expert of
        the wrong layer or slot reads other weights."""
        if case == "mixtral":
            cfg = get_config("mixtral-8x22b").scaled(
                num_layers=3, scan_repeats=3, d_model=64, num_heads=4,
                num_kv_heads=2, head_dim=16, d_ff=64, moe_d_ff=64,
                num_experts=4, top_k=2, window=16, vocab_size=256,
                dtype="float32")
            assert not cfg.experts_held
        else:
            cfg = small_cfg(num_layers=3, prefix_kinds=(), scan_repeats=3,
                            dtype="float32")
        params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        moe = params["scan"]["b0"]["moe"]
        for w in transformer.EXPERT_WEIGHTS:
            *lead, rows, cols = moe[w].shape
            one = np.zeros((*lead, rows, cols), np.float32)
            at = np.indices((*lead, cols))
            one[(*at[:-1], rng.integers(0, rows, (*lead, cols)), at[-1])] = \
                rng.choice([-1.0, 1.0], (*lead, cols))
            moe[w] = jnp.asarray(one)
        lanes, b, s = {"lanes": (3, 1, 4), "idle_expert": (None, 1, 3)}.get(
            case, (None, 2, 4))
        q_lens = jnp.array([4, 1]) if case == "padding" else None
        shape = (b, s, cfg.d_model) if lanes is None else \
            (lanes, b, s, cfg.d_model)
        x = jax.random.normal(jax.random.PRNGKey(5), shape)
        cache = get_model(cfg).init_cache(cfg, b, 8)

        def in_place(x):
            out, _, _, load = transformer._run_stack(cfg, params, cache, x,
                                                     pos=0, q_lens=q_lens)
            return out, load

        def by_layer(x):
            loads = []
            for r in range(cfg.scan_repeats):
                p, c = jax.tree_util.tree_map(
                    lambda a: a[r], (params["scan"], cache["scan"]))
                x, _, load = transformer.block_apply(
                    cfg.scan_pattern[0], cfg, p["b0"], x, cache=c["b0"],
                    pos=0, q_lens=q_lens, dropless=True)
                loads.append(load)
            return x, jnp.stack(loads)

        if lanes is not None:
            in_place, by_layer = jax.vmap(in_place), jax.vmap(by_layer)
        with jax.disable_jit():
            got, want = in_place(x), by_layer(x)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        load = np.asarray(got[1]).reshape(-1, cfg.scan_repeats,
                                          cfg.n_experts_held).sum(0)
        assert (load.sum(-1) > 0).all()
        if case == "idle_expert":      # idle in a layer, busy in another
            assert ((load == 0) & (load > 0).any(0)).any()
        if case == "padding":
            assert load.sum() < cfg.scan_repeats * b * s * cfg.top_k


# ---------------------------------------------------------------------------
# the shared-latent kernel's q block
# ---------------------------------------------------------------------------

class TestLatentQBlock:
    @pytest.mark.parametrize("qn,want", [(1, 1), (16, 4), (64, 4), (6, 3)])
    def test_sized_from_vmem_at_published_widths(self, qn, want):
        qb = latent_q_block(qn, 0, 128, 512, 64, 64)
        assert qb == want and qn % qb == 0
        assert qb * 128 * q_row_bytes(1, 512, 512, 64, 64) <= VMEM_BUDGET

    def test_a_tuned_block_is_kept_when_it_fits(self):
        assert latent_q_block(16, 2, 128, 512, 64, 64) == 2
        assert latent_q_block(8, 0, 4, 32, 16, 8) == 8


# ---------------------------------------------------------------------------
# the whole mixed step against the plain reference
# ---------------------------------------------------------------------------

REQS = [(13, 6), (5, 9), (20, 4)]


def _serve_recording(engine, reqs, **kw):
    """Serve ``reqs`` through the pallas_paged mixed step -> (requests,
    {(prompt index, position): logits row}) for every real row of every
    step, matched to its request by the tokens it carried."""
    rows = {}
    inner = engine.mixed_step

    def step(params, kcache, table, toks, poss, q_lens, **k):
        out = inner(params, kcache, table, toks, poss, q_lens, **k)
        rows_of_step.append((np.asarray(toks), np.asarray(poss),
                             np.asarray(q_lens), np.asarray(out[0])))
        return out

    rows_of_step = []
    engine.mixed_step = step
    sched = Scheduler(engine, batch_size=2, slot_len=64, prefill_chunk=4,
                      kv_page_size=8, attn_backend="pallas_paged",
                      buckets=(64,), **kw)
    done = [sched.submit(p, g) for p, g in reqs]
    sched.run()
    engine.mixed_step = inner
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1])])
            for r in done]
    for toks, poss, q_lens, logits in rows_of_step:
        for s in np.nonzero(q_lens)[0]:
            q, pos = int(q_lens[s]), int(poss[s])
            (i,) = [i for i, seq in enumerate(seqs)
                    if len(seq) >= pos + q and
                    (seq[pos:pos + q] == toks[s, :q]).all()]
            for j in range(q):
                rows[(i, pos + j)] = logits[s, j]
    return done, rows


def test_mixed_step_logits_match_the_reference():
    """Chunked prefill, then cached decode, through the scheduler's
    paged mixed step (float32, uncompressed): every position's logits
    against the reference's teacher-forced full forward.  The two differ
    in arithmetic only -- the program attends in MLA's absorbed form over
    the latent pages, sorts pairs into grouped products and sums the
    online softmax page by page; the reference scores per head in the
    published form with dense experts -- so they agree to float32
    rounding, 1e-4 of logits of order 1; a bfloat16 rounding anywhere
    would show as 1e-2."""
    cfg = small_cfg()          # weights drawn in bf16 as the reference
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda k: get_model(cfg).init_params(cfg, k))(key)
    cfg = cfg.scaled(dtype="float32")
    ref = load_reference()
    m = model_block(cfg)
    ref_params = ref.init_params(m, key)
    engine = ServeEngine(cfg, jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), params), compress=False)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), g)
            for n, g in REQS]
    done, rows = _serve_recording(engine, reqs)
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1])])
            for r in done]
    want = ref.logits_at(m, ref_params, seqs,
                         [np.arange(len(s)) for s in seqs], "float32")
    assert len(rows) == sum(len(s) for s in seqs)
    worst = max(float(np.abs(rows[(i, j)] - want[i][j]).max())
                for i, s in enumerate(seqs) for j in range(len(s)))
    scale = max(float(np.abs(w).max()) for w in want)
    assert 0.1 < scale < 10 and worst < 1e-4, (worst, scale)
    for i, r in enumerate(done):
        assert list(r.generated) == list(
            want[i][len(r.prompt) - 1:].argmax(-1))


def test_expert_counters_span_and_prometheus():
    """The mixed step's expert pairs come back with its tokens: counted
    in ServeMetrics (every step, every MoE block), put on the
    ``mixed_step`` span as ``expert_pairs`` and exported to Prometheus."""
    cfg = small_cfg()
    params = jax.tree_util.tree_map(np.asarray, get_model(cfg).init_params(
        cfg, jax.random.PRNGKey(0)))
    tel = Telemetry(trace=True)
    engine = ServeEngine(cfg, params, compress=True, telemetry=tel)
    assert engine.api.expert_load
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), g)
            for n, g in REQS]
    sched = Scheduler(engine, batch_size=2, slot_len=64, prefill_chunk=4,
                      kv_page_size=8, attn_backend="pallas_paged",
                      buckets=(64,))
    fed = []
    record = engine.metrics.record_expert_load
    engine.metrics.record_expert_load = \
        lambda load: fed.append(np.asarray(load)) or record(load)
    for p, g in reqs:
        sched.submit(p, g)
    sched.run()
    m = engine.metrics
    steps = tel.phases["mixed_step"].n
    assert len(fed) == steps
    assert m.expert_groups_live == sum(int((f > 0).sum()) for f in fed)
    assert 0 < m.expert_groups_live < steps * cfg.scan_repeats * \
        cfg.n_experts_held
    assert m.expert_steps == steps
    assert m.expert_block_steps == steps * cfg.scan_repeats
    assert 0 < m.expert_busiest_pairs <= m.expert_pairs
    assert 0 < m.expert_busiest_share_sum <= steps
    spans = [e for e in tel.tracer.chrome()["traceEvents"]
             if e.get("name") == "mixed_step"]
    assert len(spans) == steps
    assert sum(e["args"]["expert_pairs"] for e in spans) == m.expert_pairs
    prom = engine.render_prom()
    assert f"repro_expert_pairs_total {m.expert_pairs}" in prom
    assert f"repro_expert_steps_total {steps}" in prom
    assert f"repro_expert_groups_live_total {m.expert_groups_live}" in prom
