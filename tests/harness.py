"""Shared scheduler test harness: engine factory + trace runner +
token-equivalence assertion.

Every serving suite used to carry its own copy of this boilerplate
(engine construction over the reduced configs, a submit/run/collect loop,
and a dict-equality check); it lives here once so a new suite — or a new
serving feature like prefix sharing — tests token equivalence against the
oracle in three lines.  ``run_trace`` returns ``{request index: generated
token tuple}`` keyed by submission order, so two runs over the same
request list compare directly regardless of scheduling order.
"""

import jax
import numpy as np

from repro.models.api import get_model
from repro.runtime import Scheduler, ServeEngine
from tests.test_models import reduced

# the canonical mixed-length (prompt_len, gen) trace: short/long prompts
# and budgets interleaved so admission, chunking, paging, and retire all
# overlap (suites that need a smaller trace slice it)
MIXED = [(5, 7), (12, 2), (20, 5), (6, 9), (3, 1), (9, 4)]


def make_engine(arch="minitron-8b", seed=0, **engine_kw):
    """ServeEngine over a reduced float32 config, with compressed MLPs
    unless ``compress=False``."""
    cfg = reduced(arch)
    params = jax.tree_util.tree_map(
        np.asarray, get_model(cfg).init_params(cfg, jax.random.PRNGKey(seed)))
    engine_kw.setdefault("compress", True)
    return ServeEngine(cfg, params, **engine_kw)


def mixed_requests(engine, trace=MIXED, seed=7):
    """Deterministic (prompt, gen) pairs for a (prompt_len, gen) trace."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, engine.cfg.vocab_size, L), g)
            for L, g in trace]


def run_trace(engine, reqs, **kw):
    """Serve ``reqs`` through a fresh Scheduler -> {request index:
    generated token tuple}, keyed by submission order."""
    kw.setdefault("batch_size", 2)
    kw.setdefault("buckets", (32,))
    sched = Scheduler(engine, **kw)
    rids = {}
    for i, r in enumerate(reqs):
        rids[sched.submit(*r).rid] = i
    done = sched.run()
    assert len(done) == len(reqs), (len(done), len(reqs))
    return {rids[r.rid]: tuple(r.generated) for r in done}


def assert_tokens_identical(got, want, label=""):
    """Per-request token equality with a readable first-divergence
    message (dict inequality alone points at nothing)."""
    assert set(got) == set(want), \
        f"{label}: request sets differ: {sorted(got)} vs {sorted(want)}"
    for i in sorted(want):
        assert got[i] == want[i], \
            f"{label}: request {i} diverged:\n  got  {got[i]}\n" \
            f"  want {want[i]}"
