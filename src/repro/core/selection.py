"""Probabilistic client selection — paper Eq (12) + baselines.

``HeteRo-Select``: softmax over scores with dynamic temperature
τ(t) = τ0·(1 − 0.5·min(t/100, 1)), then probability-weighted sampling of m
clients *without replacement* (Gumbel-top-m — exact for the Plackett–Luce
model induced by the softmax).

Baselines (paper Sec V):
  * ``random``          — FedAvg-style uniform sampling [McMahan et al. 17].
  * ``power_of_choice`` — sample d candidates uniformly, keep the m with the
                          highest local loss [Cho et al. 20].
  * ``oort``            — statistical utility with an exploitation/
                          exploration split, a participation staleness term
                          and the system-utility straggler penalty
                          (speeds from fed.availability.SystemProfile)
                          [Lai et al., OSDI 21].

Zoo extensions (docs/selectors.md):
  * ``filtered`` / ``filtered:<base>`` — FilFL-style pre-selection filtering
    [arXiv:2302.06599]: prune the candidate pool to the top keep_frac·K
    clients by optimistic utility *before* the base scoring selector
    (heterosel / heterosel_mult / random / oort) samples from it. The
    sampled cohort is guaranteed to be a subset of the surviving pool.
  * ``adaptive``        — heterogeneity-guided sampling [arXiv:2310.00198]:
    the softmax temperature is rescaled online by the observed dispersion
    (coefficient of variation) of client losses, flattening selection when
    the fleet is heterogeneous and sharpening it as losses equalize. Under
    topology='hierarchical' the engine pairs it with the
    `core.adaptive.AdaptiveBudgets` controller, which retunes the per-edge
    ``edge_budgets`` online from observed edge utility.

Every selector is a pure function
``(key, state, round_idx) -> (selected_mask, probs)`` so the whole FL loop
stays jittable.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.scoring import HeteRoScoreConfig, compute_scores
from repro.core.state import ClientState, staleness as _staleness

SelectFn = Callable[[jax.Array, ClientState, jax.Array], Tuple[jax.Array, jax.Array]]
# Async variant: a fourth (K,) float argument carries real per-client
# staleness measured by the virtual wall clock (fed.clock) instead of the
# round counter — see make_async_selector.
AsyncSelectFn = Callable[
    [jax.Array, ClientState, jax.Array, jax.Array], Tuple[jax.Array, jax.Array]
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SelectorConfig:
    """Selection-policy hyper-parameters (paper Sec III-B.6)."""

    num_selected: int = 6          # m — clients per round (50% of 12)
    tau0: float = 1.0              # base softmax temperature τ0
    tau_decay_rounds: int = 100    # the /100 in τ(t)
    additive: bool = True          # Eq (1) vs Eq (2)
    poc_candidates: int = 0        # Power-of-Choice d (0 ⇒ 2m)
    oort_explore_frac: float = 0.1 # Oort ε — fraction of slots for exploration
    oort_staleness_coef: float = 0.1
    oort_system_alpha: float = 2.0 # Oort system-utility exponent
    # Score+softmax via the fused Pallas kernel (kernels.score_select) —
    # single-pass over the (K,) metadata vectors; additive form only.
    # Large-K path: the struct-of-arrays ClientState feeds it directly.
    use_fused_kernel: bool = False
    # 'filtered' pre-selection stage (FilFL): keep ceil(keep_frac·K) clients
    # (never fewer than m) by optimistic loss + diversity before scoring.
    filter_keep_frac: float = 0.5
    filter_diversity_coef: float = 0.5
    # 'adaptive' temperature controller: τ ← τ(t)·clip(1 + gain·(cv − ref),
    # scale_min, scale_max) where cv is the observed-loss coefficient of
    # variation (cold start ⇒ scale 1, i.e. plain heterosel).
    tau_adapt_gain: float = 2.0
    tau_adapt_ref: float = 0.25
    tau_scale_min: float = 0.5
    tau_scale_max: float = 2.0


def dynamic_temperature(round_idx: jax.Array, cfg: SelectorConfig) -> jax.Array:
    """τ(t) = τ0 · (1 − 0.5·min(t/100, 1)) — Eq (12) / Sec III-B.6."""
    t = jnp.asarray(round_idx, jnp.float32)
    return cfg.tau0 * (1.0 - 0.5 * jnp.minimum(t / cfg.tau_decay_rounds, 1.0))


def selection_probabilities(scores: jax.Array, tau: jax.Array) -> jax.Array:
    """Eq (12): p_k = softmax(S_k / τ) over the available-client set."""
    return jax.nn.softmax(scores / tau)


def sample_clients(key: jax.Array, probs: jax.Array, m: int) -> jax.Array:
    """Sample m distinct clients ∝ probs via Gumbel-top-m; returns bool mask.

    Gumbel-top-m over log p is an exact sampler for successive sampling
    without replacement from the softmax distribution.
    """
    g = jax.random.gumbel(key, probs.shape, probs.dtype)
    perturbed = jnp.log(probs + 1e-30) + g
    _, idx = jax.lax.top_k(perturbed, m)
    return jnp.zeros_like(probs, dtype=bool).at[idx].set(True)


# ---------------------------------------------------------------------------
# Selector implementations
# ---------------------------------------------------------------------------


def heterosel_select(
    key: jax.Array,
    state: ClientState,
    round_idx: jax.Array,
    *,
    sel_cfg: SelectorConfig,
    score_cfg: HeteRoScoreConfig,
    staleness_override: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """HeteRo-Select: Algorithm 1, phases 1–2.

    With ``sel_cfg.use_fused_kernel`` the six score components + softmax +
    Gumbel-top-m sampling run as the two-pass multi-block Pallas kernel over
    the struct-of-arrays state (``kernels.score_select``) — the production
    large-K path, compiled on TPU and interpreted on CPU (tests only).
    Additive form only. The in-kernel sampler draws the same Gumbel noise as
    :func:`sample_clients`, so for a given key the fused cohort matches the
    jnp path's.

    ``staleness_override`` substitutes a (K,) clock-measured staleness for
    the round counter in the freshness term (Eq 7) — the async engine's
    path. Both the jnp and the fused-kernel branch accept it (the kernel
    carries the override as a ninth stacked row).
    """
    tau = dynamic_temperature(round_idx, sel_cfg)
    if sel_cfg.use_fused_kernel:
        if not sel_cfg.additive:
            raise ValueError("fused scoring kernel implements the additive form only")
        from repro.kernels import ops as kernel_ops  # deferred: pallas optional

        selected, probs, _ = kernel_ops.heterosel_topm(
            state, jnp.asarray(round_idx, jnp.float32), tau,
            sel_cfg.num_selected, key, score_cfg,
            staleness_override=staleness_override,
            interpret=kernel_ops.kernel_interpret(),
        )
        mask = jnp.zeros((state.num_clients,), bool).at[selected].set(True)
        return mask, probs
    scores = compute_scores(state, round_idx, score_cfg,
                            additive=sel_cfg.additive,
                            staleness_override=staleness_override)
    probs = selection_probabilities(scores, tau)
    mask = sample_clients(key, probs, sel_cfg.num_selected)
    return mask, probs


def random_select(
    key: jax.Array, state: ClientState, round_idx: jax.Array, *, sel_cfg: SelectorConfig
) -> Tuple[jax.Array, jax.Array]:
    """Uniform m-of-K sampling (FedAvg baseline)."""
    k = state.num_clients
    probs = jnp.full((k,), 1.0 / k, jnp.float32)
    mask = sample_clients(key, probs, sel_cfg.num_selected)
    return mask, probs


def power_of_choice_select(
    key: jax.Array, state: ClientState, round_idx: jax.Array, *, sel_cfg: SelectorConfig
) -> Tuple[jax.Array, jax.Array]:
    """Power-of-Choice: d uniform candidates, keep top-m by local loss.

    Unobserved clients carry loss 0 in ``loss_prev``; PoC treats them as
    high-value by assigning them the current max loss (optimistic init) —
    otherwise the method can never discover anyone, which is not what the
    original algorithm (which assumes an oracle loss) does.

    Loss ties (e.g. all-equal optimistic inits at round 0) are broken by a
    per-candidate jitter drawn from the second split of the key — without
    it ``lax.top_k`` resolves ties by index order and permanently biases
    low client ids.
    """
    k = state.num_clients
    m = sel_cfg.num_selected
    d = sel_cfg.poc_candidates or min(2 * m, k)
    kc, kt = jax.random.split(key)
    cand = sample_clients(kc, jnp.full((k,), 1.0 / k, jnp.float32), d)
    opt_loss = jnp.where(state.has_loss > 0, state.loss_prev, jnp.max(state.loss_prev) + 1.0)
    jitter = jax.random.uniform(kt, (k,), jnp.float32, 0.0, 1e-6)
    cand_loss = jnp.where(cand, opt_loss + jitter, -jnp.inf)
    _, idx = jax.lax.top_k(cand_loss, m)
    mask = jnp.zeros((k,), bool).at[idx].set(True)
    probs = cand.astype(jnp.float32) / d  # candidate distribution (diagnostic)
    return mask, probs


def oort_select(
    key: jax.Array, state: ClientState, round_idx: jax.Array, *,
    sel_cfg: SelectorConfig, speeds: Optional[jax.Array] = None,
    staleness_override: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Oort's guided selection: statistical × system utility + explore split.

    util_k = loss_k · (1 + c·√staleness) · min(1, speed_k)^α — the system
    term penalizes clients slower than the preferred round duration
    (``speeds`` = T_pref/t_k from fed.availability.SystemProfile; omit for a
    homogeneous fleet). A fraction ε of the m slots goes to never-explored
    clients chosen uniformly; the exploit slots are greedy top-by-utility.
    ``staleness_override`` replaces the round-counter staleness with the
    async clock's measurement (make_async_selector threads it).
    """
    k = state.num_clients
    m = sel_cfg.num_selected
    m_explore = max(int(round(sel_cfg.oort_explore_frac * m)), 1)
    m_exploit = m - m_explore
    kx, ke = jax.random.split(key)

    if staleness_override is None:
        stale = _staleness(state, round_idx).astype(jnp.float32)
    else:
        stale = jnp.maximum(jnp.asarray(staleness_override, jnp.float32), 0.0)
    util = state.loss_prev * (1.0 + sel_cfg.oort_staleness_coef * jnp.sqrt(jnp.minimum(stale, 100.0)))
    if speeds is not None:
        sys_util = jnp.minimum(jnp.asarray(speeds, jnp.float32), 1.0) ** sel_cfg.oort_system_alpha
        util = util * sys_util
    explored = state.has_loss > 0
    exploit_util = jnp.where(explored, util, -jnp.inf)
    _, exploit_idx = jax.lax.top_k(exploit_util, m_exploit)
    mask = jnp.zeros((k,), bool).at[exploit_idx].set(True)
    # Exploration slots: uniform over unexplored (fall back to uniform-all).
    unexplored = (~explored) & (~mask)
    any_unexplored = jnp.any(unexplored)
    w = jnp.where(unexplored, 1.0, jnp.where(any_unexplored, 0.0, (~mask).astype(jnp.float32)))
    w = w / jnp.maximum(jnp.sum(w), 1e-9)
    emask = sample_clients(ke, w, m_explore)
    mask = mask | emask
    probs = jax.nn.softmax(jnp.where(jnp.isfinite(exploit_util), exploit_util, -1e9))
    return mask, probs


# ---------------------------------------------------------------------------
# FilFL-style pre-selection filtering (arXiv:2302.06599)
# ---------------------------------------------------------------------------


def filter_pool_size(num_clients: int, sel_cfg: SelectorConfig) -> int:
    """Static pool size: ceil(keep_frac·K), never below m, never above K."""
    n_keep = math.ceil(sel_cfg.filter_keep_frac * num_clients)
    return min(max(n_keep, sel_cfg.num_selected), num_clients)


def filter_pool(state: ClientState, sel_cfg: SelectorConfig) -> jax.Array:
    """(K,) bool — the surviving candidate pool, pure and jittable.

    Filtering utility is the *optimistic* local loss (unobserved clients
    get max+1, so exploration is never filtered away) plus a
    ``filter_diversity_coef``-weighted label-divergence bonus: FilFL prunes
    clients whose participation is unlikely to help the global objective,
    and under label skew those are exactly the low-loss, low-divergence
    ones. Deterministic given the state (top-k, index-ordered ties).
    """
    n_keep = filter_pool_size(state.num_clients, sel_cfg)
    opt_loss = jnp.where(state.has_loss > 0, state.loss_prev,
                         jnp.max(state.loss_prev) + 1.0)
    util = opt_loss + sel_cfg.filter_diversity_coef * state.label_js
    _, idx = jax.lax.top_k(util, n_keep)
    return jnp.zeros((state.num_clients,), bool).at[idx].set(True)


def _pool_resample(key: jax.Array, probs: jax.Array, pool: jax.Array,
                   m: int) -> Tuple[jax.Array, jax.Array]:
    """Renormalize ``probs`` onto ``pool`` and Gumbel-top-m sample inside it.

    Pruned clients are excluded with a hard −inf in the perturbed Gumbel
    scores (a probability floor is not enough: softmax underflow plus
    Gumbel ties could leak a pruned client through ``top_k``). Because the
    pool always holds ≥ m members (`filter_pool_size`), exactly m pool
    clients are selected. Mirrors `fed.availability._remask`: the sampling
    key is ``fold_in(key, 1)`` so a wrapped base selector's own draw (whose
    mask we discard) cannot collide with ours.
    """
    probs = jnp.where(pool, probs, 0.0)
    norm = jnp.sum(probs)
    uniform_pool = pool.astype(probs.dtype) / jnp.maximum(jnp.sum(pool), 1)
    probs = jnp.where(norm > 1e-9, probs / jnp.maximum(norm, 1e-9), uniform_pool)
    g = jax.random.gumbel(jax.random.fold_in(key, 1), probs.shape, probs.dtype)
    perturbed = jnp.where(pool, jnp.log(probs + 1e-30) + g, -jnp.inf)
    _, idx = jax.lax.top_k(perturbed, m)
    mask = jnp.zeros_like(pool).at[idx].set(True)
    return mask & pool, probs


# Scoring bases the filtering stage composes with. power_of_choice is
# excluded on purpose: its ``probs`` output is a candidate-set diagnostic,
# not a scoring distribution to renormalize. heterosel_pallas is excluded
# because the fused kernel samples in-kernel — filtering would silently
# discard its cohort and resample host-side, defeating the kernel.
FILTER_BASES = ("heterosel", "heterosel_mult", "random", "oort")


def base_selector_name(name: str) -> str:
    """'filtered:<base>' → '<base>'; 'filtered' → 'heterosel'; else name.

    The hierarchical engine uses this to decide outer-stage semantics
    (uniform vs pooled HeteRo scoring) for composite selector names.
    """
    if name == "filtered":
        return "heterosel"
    if name.startswith("filtered:"):
        return name.split(":", 1)[1]
    return name


def _filter_base(name: str) -> str:
    base = base_selector_name(name)
    if base not in FILTER_BASES:
        raise ValueError(
            f"filtered selector cannot wrap '{base}': valid bases are "
            f"{FILTER_BASES}")
    return base


# ---------------------------------------------------------------------------
# Heterogeneity-guided adaptive sampling (arXiv:2310.00198)
# ---------------------------------------------------------------------------


def heterogeneity_scale(state: ClientState, sel_cfg: SelectorConfig) -> jax.Array:
    """Temperature rescale from the observed-loss coefficient of variation.

    cv = std/mean over clients with an observed loss; scale =
    clip(1 + gain·(cv − ref), scale_min, scale_max). No observations yet ⇒
    cv := ref ⇒ scale 1 (plain heterosel). Pure function of the
    checkpointed ClientState, so the adaptive selector stays jit-pure,
    seed-deterministic and resume-bitwise with no host-side controller.
    """
    obs = (state.has_loss > 0).astype(jnp.float32)
    n = jnp.sum(obs)
    mean = jnp.sum(state.loss_prev * obs) / jnp.maximum(n, 1.0)
    var = jnp.sum(obs * (state.loss_prev - mean) ** 2) / jnp.maximum(n, 1.0)
    cv = jnp.sqrt(var) / jnp.maximum(jnp.abs(mean), 1e-6)
    cv = jnp.where(n > 0, cv, sel_cfg.tau_adapt_ref)
    scale = 1.0 + sel_cfg.tau_adapt_gain * (cv - sel_cfg.tau_adapt_ref)
    return jnp.clip(scale, sel_cfg.tau_scale_min, sel_cfg.tau_scale_max)


def adaptive_select(
    key: jax.Array,
    state: ClientState,
    round_idx: jax.Array,
    *,
    sel_cfg: SelectorConfig,
    score_cfg: HeteRoScoreConfig,
    staleness_override: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """HeteRo scoring with a heterogeneity-adaptive softmax temperature.

    High loss dispersion across the fleet ⇒ hotter softmax (broader
    exploration of the heterogeneous tail); converging, equalized losses ⇒
    cooler softmax (sharper exploitation). ``staleness_override`` threads
    the async clock's staleness into the Eq-7 freshness term exactly as in
    `heterosel_select`.
    """
    tau = dynamic_temperature(round_idx, sel_cfg) * heterogeneity_scale(state, sel_cfg)
    scores = compute_scores(state, round_idx, score_cfg,
                            additive=sel_cfg.additive,
                            staleness_override=staleness_override)
    probs = selection_probabilities(scores, tau)
    mask = sample_clients(key, probs, sel_cfg.num_selected)
    return mask, probs


def edge_selection_probs(
    pooled_state: ClientState,
    round_idx: jax.Array,
    sel_cfg: SelectorConfig,
    score_cfg: HeteRoScoreConfig,
) -> jax.Array:
    """(E,) cross-edge selection probabilities for the hierarchical outer
    stage (docs/hierarchy.md).

    ``pooled_state`` is the (E,)-sized pseudo-client state produced by
    ``core.state.pool_client_state`` — each row pools one edge group's
    metadata — so the paper's score machinery (Eqs 1–11 + the Eq-12 softmax
    with dynamic temperature) runs on edge aggregates unchanged. Sampling
    itself stays with the caller (the hierarchical engine masks busy edges
    host-side before its Gumbel-top-m draw, which a pure jitted function
    cannot express with a round-varying edge count).
    """
    scores = compute_scores(pooled_state, round_idx, score_cfg,
                            additive=sel_cfg.additive)
    tau = dynamic_temperature(round_idx, sel_cfg)
    return selection_probabilities(scores, tau)


def make_selector(
    name: str,
    sel_cfg: SelectorConfig,
    score_cfg: HeteRoScoreConfig | None = None,
    *,
    speeds: Optional[jax.Array] = None,
) -> SelectFn:
    """Factory over the registry names in ``SELECTORS``.

    ``filtered`` composes: ``'filtered'`` wraps heterosel, and
    ``'filtered:<base>'`` wraps any name in ``FILTER_BASES`` — the base's
    probabilities are renormalized onto the surviving pool and the cohort
    is drawn strictly inside it.

    ``speeds`` (K,) enables Oort's system-utility term on heterogeneous
    fleets (fed.availability.SystemProfile.speeds()).
    """
    score_cfg = score_cfg or HeteRoScoreConfig()
    if name == "filtered" or name.startswith("filtered:"):
        base = make_selector(_filter_base(name), sel_cfg, score_cfg,
                             speeds=speeds)

        def filtered(key, state, round_idx):
            _, probs = base(key, state, round_idx)
            pool = filter_pool(state, sel_cfg)
            return _pool_resample(key, probs, pool, sel_cfg.num_selected)

        return filtered
    if name == "adaptive":
        return functools.partial(adaptive_select, sel_cfg=sel_cfg,
                                 score_cfg=score_cfg)
    if name == "heterosel":
        return functools.partial(heterosel_select, sel_cfg=sel_cfg, score_cfg=score_cfg)
    if name == "heterosel_pallas":
        fused = dataclasses.replace(sel_cfg, use_fused_kernel=True, additive=True)
        return functools.partial(heterosel_select, sel_cfg=fused, score_cfg=score_cfg)
    if name == "heterosel_mult":
        mult = dataclasses.replace(sel_cfg, additive=False)
        return functools.partial(heterosel_select, sel_cfg=mult, score_cfg=score_cfg)
    if name == "random":
        return functools.partial(random_select, sel_cfg=sel_cfg)
    if name == "power_of_choice":
        return functools.partial(power_of_choice_select, sel_cfg=sel_cfg)
    if name == "oort":
        return functools.partial(oort_select, sel_cfg=sel_cfg, speeds=speeds)
    raise ValueError(
        f"unknown selector '{name}': valid names are {SELECTORS} "
        f"(plus 'filtered:<base>' for base in {FILTER_BASES})")


def make_async_selector(
    name: str,
    sel_cfg: SelectorConfig,
    score_cfg: HeteRoScoreConfig | None = None,
    *,
    speeds: Optional[jax.Array] = None,
) -> AsyncSelectFn:
    """Factory for 4-arg selectors: ``(key, state, round_idx, staleness)``.

    The async engine (``fed.async_engine``) measures per-client staleness on
    its virtual wall clock — elapsed time since the client's update was last
    aggregated, in units of the reference round duration — and passes it
    here each dispatch; the HeteRo-Select freshness bonus (Eq 7) and Oort's
    staleness term then reward genuinely stale clients instead of trusting
    synchronous round counters. Selectors with no freshness term (random,
    power_of_choice) accept and ignore the extra argument, so every selector
    name works in async mode — including ``heterosel_pallas``, whose kernel
    carries the clock override as a ninth stacked row. ``filtered`` threads
    staleness through to its base for scoring; the pool itself depends only
    on the (staleness-free) optimistic loss + diversity utility. ``adaptive``
    threads it into the Eq-7 freshness term like heterosel.
    """
    score_cfg = score_cfg or HeteRoScoreConfig()
    if name == "filtered" or name.startswith("filtered:"):
        base = make_async_selector(_filter_base(name), sel_cfg, score_cfg,
                                   speeds=speeds)

        def filtered_async(key, state, round_idx, stale):
            _, probs = base(key, state, round_idx, stale)
            pool = filter_pool(state, sel_cfg)
            return _pool_resample(key, probs, pool, sel_cfg.num_selected)

        return filtered_async
    if name == "adaptive":

        def adaptive_async(key, state, round_idx, stale):
            return adaptive_select(key, state, round_idx, sel_cfg=sel_cfg,
                                   score_cfg=score_cfg,
                                   staleness_override=stale)

        return adaptive_async
    if name in ("heterosel", "heterosel_mult", "heterosel_pallas"):
        if name == "heterosel":
            cfg = sel_cfg
        elif name == "heterosel_mult":
            cfg = dataclasses.replace(sel_cfg, additive=False)
        else:
            cfg = dataclasses.replace(sel_cfg, use_fused_kernel=True,
                                      additive=True)

        def heterosel_async(key, state, round_idx, stale):
            return heterosel_select(key, state, round_idx, sel_cfg=cfg,
                                    score_cfg=score_cfg,
                                    staleness_override=stale)

        return heterosel_async
    if name == "oort":

        def oort_async(key, state, round_idx, stale):
            return oort_select(key, state, round_idx, sel_cfg=sel_cfg,
                               speeds=speeds, staleness_override=stale)

        return oort_async
    if name in ("random", "power_of_choice"):
        base = make_selector(name, sel_cfg, score_cfg)

        def stateless_async(key, state, round_idx, stale):
            return base(key, state, round_idx)

        return stateless_async
    raise ValueError(
        f"unknown selector '{name}': valid names are {SELECTORS} "
        f"(plus 'filtered:<base>' for base in {FILTER_BASES})")


SELECTORS = ("heterosel", "heterosel_pallas", "heterosel_mult", "random",
             "power_of_choice", "oort", "filtered", "adaptive")
