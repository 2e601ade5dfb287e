"""Population trial engines: K HPO trials in one (possibly sharded) program.

Serial HPO evaluates trials as independent Python jobs — each pays its own
XLA compile and runs one small model at a time, leaving the accelerator
mostly idle.  Because ``make_hparam_train_step`` takes the tunable knobs as a
*traced* ``HParams`` pytree, a whole population of trials of one architecture
can instead ride a leading ``vmap`` axis: one jitted program advances all K
trials per step, amortizing both compilation (exactly one, regardless of how
many trials the experiment runs) and per-step dispatch.

Two engines share the same population-step semantics:

* **vmapped** (``get_compiled_population_step``) — all K trials on one device;
* **sharded** (``get_compiled_sharded_population_step``) — the population axis
  is split over an N-device mesh with ``shard_map`` (K % N == 0; callers pad
  with 0-budget trials), so each device runs a K/N-wide vmapped step and the
  whole population is still ONE compiled program.  There is no cross-trial
  communication, so sharding the K axis is embarrassingly parallel — the mesh
  only changes *where* each lane's compute lands.

Population state layout::

    {"inner":     vmapped train state (leading axis K),
     "diverged":  bool[K]   — latch; a NaN/inf loss freezes that trial,
     "last_loss": f32[K]    — loss at each trial's last *applied* step}

Semantics per jitted ``pop_step(pstate, batch, hp)``:

* a trial is **active** while ``opt.step < hp.total_steps`` and not diverged —
  ``hp.total_steps`` doubles as the per-trial step budget, so trials with
  different budgets (e.g. Hyperband rungs) coexist in one batch: exhausted
  trials freeze in place while the rest continue.  Because ``total_steps`` is
  a *traced* leaf, the driver may also shrink it **mid-flight** (in-flight
  early stopping — see ``repro.core.proposer.early_stop``) without recompiling;
* a retired lane can be **refilled** in place by a lane-lifecycle op (all
  compiled, cached, with ``shard_map`` twins): ``make_lane_init`` re-inits a
  masked subset of lanes from per-lane PRNG keys, ``make_lane_splice`` updates
  exactly ONE lane via ``dynamic_update_index_in_dim`` per leaf (one init, not
  K), and ``make_lane_clone`` copies a *donor* lane's weights + optimizer
  state across the population axis (PBT exploit without a host checkpoint).
  Either way the host loop swaps the next proposal into a freed lane while the
  rest of the population keeps training — still the same compiled step program;
* a non-finite loss at an active step sets the ``diverged`` latch and the
  update is *not* applied — the sick trial freezes, the batch lives on
  (vmapped divergence masking);
* ``last_loss`` records the loss of the most recent applied update, i.e. each
  trial's own final loss once it halts.

Batch layout: with ``per_trial_batch=False`` the ``batch`` is broadcast to
every trial (``in_axes=(0, None, 0)``) — the legacy shared-stream mode.  With
``per_trial_batch=True`` every batch leaf carries a leading K axis and trial
``i`` consumes its own independently seeded stream
(``SyntheticLM.make_population_batch``), matching the serial driver when it
folds the same per-trial stream id into its PRNG.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from ..configs.base import TrainConfig
from ..distributed.sharding import (
    population_mesh,
    population_specs,
    tp_gnorm_mask,
    tp_module_flags,
    tp_shard_context,
    tp_width_rules,
    two_level_pspecs,
    two_level_state_specs,
)
from ..optim.hparams import HParams
from .train_step import (
    init_train_state,
    make_hparam_train_step,
    static_step_key,
    train_state_specs,
)

PopState = Dict[str, Any]


def _per_trial(mask: jax.Array, new, old):
    m = mask.reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old)


def init_population_state(key, tc: TrainConfig, population: int) -> PopState:
    """Initialize K identical trials from one PRNG key.

    All trials start from the same weights (the serial driver inits every
    trial with the same seed); only their traced hyperparameters differ.
    Use ``init_population_state_from_keys`` for per-trial init seeds.
    """
    return _broadcast_lanes(init_train_state(key, tc), population)


def _broadcast_lanes(one, population: int) -> PopState:
    inner = jax.tree.map(lambda x: jnp.broadcast_to(x, (population,) + x.shape), one)
    return _wrap(inner, population)


def init_population_state_from_keys(keys, tc: TrainConfig) -> PopState:
    """Initialize one trial per PRNG key (keys shape ``(K, 2)``)."""
    inner = jax.vmap(lambda k: init_train_state(k, tc))(keys)
    return _wrap(inner, int(keys.shape[0]))


def _wrap(inner, k: int) -> PopState:
    return {
        "inner": inner,
        "diverged": jnp.zeros((k,), bool),
        "last_loss": jnp.full((k,), jnp.inf, jnp.float32),
    }


# -- two-level (pop, model) mesh helpers ----------------------------------------
#
# On a two-level mesh the population axis holds ``rows`` lane rows and each
# row is ``width = mesh.size / rows`` devices of genuine tensor parallelism:
# the sharded engines shard_map over BOTH axes, partitioning every lane's
# attention heads / MLP ff / mamba channels over its own row per
# ``tp_width_rules`` with the psum seams in the model code (tp_enter /
# tp_reduce).  Width is layout, never math — a width-W program computes the
# same losses as width-1 up to fp reassociation of the seam reductions.


def _pop_rows(mesh: Mesh, axis: str = "pop") -> int:
    """Lane-row count of ``mesh`` (== device count on a 1-D population mesh)."""
    return int(dict(mesh.shape).get(axis, mesh.size))


def _mesh_width(mesh: Mesh, axis: str = "pop") -> int:
    """Model-parallel width per lane row (1 on a 1-D population mesh)."""
    return mesh.size // _pop_rows(mesh, axis)


def _mesh_cache_key(mesh: Mesh, axis: str) -> Tuple:
    # the mesh SHAPE is part of the key: the same 8 devices arranged (8,)
    # and (4, 2) compile different programs
    return (tuple(d.id for d in mesh.devices.flat), axis,
            tuple((n, int(s)) for n, s in mesh.shape.items()))


def _check_rows(population: int, mesh: Mesh, axis: str = "pop") -> None:
    rows = _pop_rows(mesh, axis)
    if population % rows:
        raise ValueError(
            f"population {population} does not divide over {rows} lane rows; "
            f"pad to {pad_population(population, mesh, axis=axis)} with "
            f"0-budget trials"
        )


def _population_state_shapes(tc: TrainConfig, population: int) -> PopState:
    return jax.eval_shape(
        lambda: init_population_state(jax.random.PRNGKey(0), tc, population))


def _state_logical_specs(tc: TrainConfig) -> Dict[str, Any]:
    return {"inner": train_state_specs(tc), "diverged": (), "last_loss": ()}


def _tp_rules_or_raise(tc: TrainConfig, width: int,
                       model_axis: str = "model"):
    rules = tp_width_rules(tc.model, width, model_axis)
    if not rules:
        raise ValueError(
            f"model-parallel width {width} shards nothing of "
            f"{tc.model.name} (heads={tc.model.n_heads}, "
            f"kv={tc.model.n_kv_heads}, ff={tc.model.d_ff}, "
            f"moe={tc.model.has_moe}) — pure replication contradicts "
            f"--model-parallel; pick a width dividing the module dims"
        )
    return rules


def _tp_state_pspecs(tc: TrainConfig, mesh: Mesh, axis: str,
                     model_axis: str = "model"):
    """(per-leaf PartitionSpec tree for the population state, width rules).

    The pspecs do not depend on the lane count (only trailing dims are
    inspected), so a placeholder K = rows is used for the shape walk."""
    width = _mesh_width(mesh, axis)
    rules = _tp_rules_or_raise(tc, width, model_axis)
    shapes = _population_state_shapes(tc, _pop_rows(mesh, axis))
    return two_level_pspecs(
        shapes, _state_logical_specs(tc), mesh, axis=axis, rules=rules), rules


def _fused_kernels_on(tc: TrainConfig) -> bool:
    """shard_map's static replication checker has no rule for pallas_call, so
    the width-1 sharded twins must drop to ``check_vma=False`` whenever a
    Pallas kernel rides inside the train step: a ``--fused-*`` flag, or any
    backend on which ``kernels.ops`` routes aligned shapes to its kernels
    (the TPU).  The width>1 twins always do: the checker cannot see through
    the custom_vjp psum seams either."""
    from ..kernels import ops

    m = tc.model
    return bool(m.fused_rmsnorm or m.fused_attention or m.fused_ssm
                or ops._use_pallas())


def _tp_body(fn: Callable, tc: TrainConfig, width: int,
             model_axis: str = "model") -> Callable:
    """Wrap a shard_map-local population fn so the TP seams are armed while
    it traces: module flags pick which seams fire, and the gnorm mask tells
    ``optim.adamw.global_norm`` which grad leaves are width-local shards."""
    flags = tp_module_flags(tc.model, width)
    rules = tp_width_rules(tc.model, width, model_axis)
    mask = tp_gnorm_mask(train_state_specs(tc)["params"], rules)

    def wrapped(*args):
        with tp_shard_context(model_axis, flags, gnorm_mask=mask):
            return fn(*args)

    return wrapped


def make_population_train_step(tc: TrainConfig, per_trial_batch: bool = False) -> Callable:
    """``(pstate, batch, hp) -> (pstate, metrics)`` over a leading K axis.

    ``hp`` is a stacked ``HParams`` (every leaf shape ``(K,)``); metrics come
    back per-trial (leading K) plus an ``active`` mask.  ``per_trial_batch``
    selects whether ``batch`` leaves carry a leading K axis (independent
    per-trial data streams) or are broadcast to every trial.
    """
    step = make_hparam_train_step(tc)
    vstep = jax.vmap(step, in_axes=(0, 0 if per_trial_batch else None, 0))

    def pop_step(pstate: PopState, batch, hp: HParams):
        inner = pstate["inner"]
        in_budget = inner["opt"]["step"].astype(jnp.float32) < hp.total_steps
        active = in_budget & ~pstate["diverged"]
        new_inner, metrics = vstep(inner, batch, hp)
        finite = jnp.isfinite(metrics["loss"])
        applied = active & finite
        merged = jax.tree.map(lambda n, o: _per_trial(applied, n, o), new_inner, inner)
        return {
            "inner": merged,
            "diverged": pstate["diverged"] | (active & ~finite),
            "last_loss": jnp.where(applied, metrics["loss"], pstate["last_loss"]),
        }, dict(metrics, active=active)

    return pop_step


# -- lane-lifecycle ops ---------------------------------------------------------
#
# A population lane cycles through its lifecycle inside ONE compiled flight:
# lease -> train -> retire -> refill.  The refill is a device op picked from
# this unified layer (each has a ``shard_map`` twin and a compile-once cache
# entry via ``get_compiled_lane_op``):
#
# * ``init``   (``make_lane_init``)   — re-init a masked subset of lanes from
#   per-lane PRNG keys (vmapped ``init_train_state``): the PR-3 reset, used
#   when several lanes refill at once;
# * ``clone``  (``make_lane_clone``)  — copy a *donor* lane's params AND
#   optimizer state across the population axis into the masked lanes: the
#   PBT/EAS exploit primitive (weight inheritance without a host checkpoint
#   round-trip).  Hyperparameters are not touched — they ride in the traced
#   ``HParams`` stack the host re-stacks per lease;
# * ``splice`` (``make_lane_splice``) — update ONE target lane via
#   ``dynamic_update_index_in_dim`` per leaf: a single ``init_train_state``
#   instead of vmap-initializing all K lanes and where-selecting, so splicing
#   one lane of a big model costs one lane's init, not K.


def make_lane_init(tc: TrainConfig) -> Callable:
    """``(pstate, mask, keys) -> pstate`` with masked lanes re-initialized.

    The in-place lane *refill* primitive: when the host loop retires a lane
    (budget exhausted, rung-truncated, or diverged) it can splice the next
    proposal into that lane **without leaving the compiled program** — the
    reset re-inits the lane's inner train state (params, optimizer moments,
    step counter) from its own PRNG key via a vmapped ``init_train_state``,
    clears the divergence latch, and restores the ``last_loss`` sentinel.
    ``mask`` is ``bool[K]`` (True = reset this lane); ``keys`` is ``(K, 2)``
    per-lane init keys, so a refilled lane starts from exactly the weights a
    fresh serial trial with the same key would — unmasked lanes keep training
    state untouched.
    """

    def reset(pstate: PopState, mask: jax.Array, keys: jax.Array) -> PopState:
        fresh = jax.vmap(lambda k: init_train_state(k, tc))(keys)
        inner = jax.tree.map(
            lambda f, o: _per_trial(mask, f, o), fresh, pstate["inner"]
        )
        return {
            "inner": inner,
            "diverged": jnp.where(mask, False, pstate["diverged"]),
            "last_loss": jnp.where(mask, jnp.float32(jnp.inf), pstate["last_loss"]),
        }

    return reset


# PR-3 name: the masked from-keys reset predates the unified lifecycle layer.
make_reset_lanes = make_lane_init


def make_lane_clone(tc: TrainConfig) -> Callable:
    """``(pstate, mask, donor_idx) -> pstate`` cloning donor lanes in place.

    For every masked lane ``i``, the whole inner train state (params, AdamW
    moments, master copy, step counter) becomes a copy of lane
    ``donor_idx[i]``, the divergence latch and ``last_loss`` are copied from
    the donor too, and unmasked lanes are untouched.  ``donor_idx`` is
    ``int32[K]`` (unmasked entries are ignored; pass the identity to be safe).
    This is the exploit half of Population-Based Training as a *device* op:
    a losing member inherits the winner's weights and optimizer state without
    the weights ever visiting the host.
    """

    def clone(pstate: PopState, mask: jax.Array, donor_idx: jax.Array) -> PopState:
        take = lambda x: jnp.take(x, donor_idx, axis=0)
        donated = jax.tree.map(take, pstate["inner"])
        inner = jax.tree.map(
            lambda d, o: _per_trial(mask, d, o), donated, pstate["inner"]
        )
        return {
            "inner": inner,
            "diverged": jnp.where(mask, take(pstate["diverged"]), pstate["diverged"]),
            "last_loss": jnp.where(mask, take(pstate["last_loss"]), pstate["last_loss"]),
        }

    return clone


def make_lane_splice(tc: TrainConfig) -> Callable:
    """``(pstate, lane, key) -> pstate`` re-initializing exactly one lane.

    Unlike ``make_lane_init`` — which vmap-inits all K lanes and
    where-selects the masked ones — the splice runs ONE ``init_train_state``
    and writes it into the target lane with ``dynamic_update_index_in_dim``
    per leaf.  ``lane`` is a *traced* int32 scalar, so one compiled program
    serves every lane; on a big model this is the difference between paying K
    inits and paying one.
    """

    def splice(pstate: PopState, lane: jax.Array, key: jax.Array) -> PopState:
        fresh = init_train_state(key, tc)
        inner = jax.tree.map(
            lambda o, f: jax.lax.dynamic_update_index_in_dim(
                o, f.astype(o.dtype), lane, 0
            ),
            pstate["inner"], fresh,
        )
        return {
            "inner": inner,
            "diverged": jax.lax.dynamic_update_index_in_dim(
                pstate["diverged"], jnp.asarray(False), lane, 0
            ),
            "last_loss": jax.lax.dynamic_update_index_in_dim(
                pstate["last_loss"], jnp.float32(jnp.inf), lane, 0
            ),
        }

    return splice


def make_lane_snapshot(tc: TrainConfig) -> Callable:
    """``(pstate, lane) -> lane_state`` harvesting ONE lane's full train state.

    The inverse of ``make_lane_splice``: instead of writing a fresh init into
    a lane, it reads the lane's complete state — params, optimizer moments,
    master copy, step counter, divergence latch and ``last_loss`` — as an
    unbatched pytree via ``dynamic_index_in_dim`` per leaf.  ``lane`` is a
    *traced* int32 scalar, so one compiled program snapshots any lane.  The
    caller ``device_get``s the result to host; together with the lane's
    stream word and host cursors this is everything needed to resurrect the
    trial in a fresh flight (``make_lane_restore``) — crash-safe streaming.

    Unlike the mutating lifecycle ops this one must NOT donate its input:
    the flight keeps training on ``pstate`` after the harvest.
    """

    def snapshot(pstate: PopState, lane: jax.Array):
        take = lambda x: jax.lax.dynamic_index_in_dim(x, lane, 0, keepdims=False)
        return {
            "inner": jax.tree.map(take, pstate["inner"]),
            "diverged": take(pstate["diverged"]),
            "last_loss": take(pstate["last_loss"]),
        }

    return snapshot


def make_lane_restore(tc: TrainConfig) -> Callable:
    """``(pstate, lane, snap) -> pstate`` splicing a harvested snapshot back.

    The write half of the snapshot/restore pair: like ``make_lane_splice``
    but the spliced state comes from a previously harvested lane snapshot
    (``make_lane_snapshot``) instead of a fresh ``init_train_state`` — one
    ``dynamic_update_index_in_dim`` per leaf, including the divergence latch,
    ``last_loss`` and the optimizer step counter, so the restored lane is
    bit-identical to the lane that was harvested.  ``lane`` is traced: a
    snapshot taken from lane i of a dead flight can land in any lane j of
    the new one.
    """

    def restore(pstate: PopState, lane: jax.Array, snap) -> PopState:
        put = lambda o, f: jax.lax.dynamic_update_index_in_dim(
            o, f.astype(o.dtype), lane, 0)
        return {
            "inner": jax.tree.map(put, pstate["inner"], snap["inner"]),
            "diverged": put(pstate["diverged"], snap["diverged"]),
            "last_loss": put(pstate["last_loss"], snap["last_loss"]),
        }

    return restore


def make_lane_regrid(tc: TrainConfig) -> Callable:
    """``(pstate, survivors) -> pstate'`` — the sixth lane-lifecycle op.

    At a rung boundary the cut lanes are dead weight: the flight keeps
    stepping K lanes while only the survivors still train.  The regrid
    gathers the survivors' FULL train state (params, optimizer moments,
    master copy, step counter, divergence latch, ``last_loss``) into a
    compact K' = len(survivors) population — ``jnp.take`` on the lane axis
    per leaf, the whole-population generalization of the single-lane
    snapshot/restore pair.  ``survivors`` is int32[K'] of surviving lane
    indices in ascending order (order preservation is what keeps the
    staggered rule's lane-order appends identical across regrids); callers
    pad it by repeating a survivor whose padded copy gets a 0-step budget.

    Resharding changes layout, never math: the compact state is the same
    bits the survivors held at K lanes, and ``regrid_population_state``
    then ``device_put``s it onto a new (fewer-lanes x wider) two-level mesh
    so later rungs train fewer trials wider instead of idling freed devices.
    Like ``snapshot`` this op must NOT donate: K' differs from K, so the
    input buffers are never reusable, and the driver drops the old state.
    """

    def regrid(pstate: PopState, survivors: jax.Array) -> PopState:
        take = lambda x: jnp.take(x, survivors, axis=0)
        return jax.tree.map(take, pstate)

    return regrid


def make_sharded_lane_regrid(tc: TrainConfig, mesh: Mesh, axis: str = "pop") -> Callable:
    """Mesh twin of the regrid gather.  The output lane count K' differs
    from K and survivors cross lane blocks, so there is no ``shard_map``
    formulation — the jitted gather runs under GSPMD (which lowers the
    cross-device ``take``), and the caller re-lays the compact state out on
    the *new* mesh with ``device_put`` (``regrid_population_state``)."""
    return make_lane_regrid(tc)


def plan_regrid(n_devices: int, n_survivors: int) -> Tuple[int, int, int]:
    """``(rows, width, lanes)`` geometry for S survivors over N devices.

    ``rows`` is the largest divisor of N such that laying the survivors out
    contiguously (``ceil(S / rows)`` lanes per row, padding at the tail)
    leaves **no device row idle** — the full-occupancy invariant the elastic
    engine maintains after every cut.  ``width = N / rows`` devices then
    serve each lane row, and ``lanes = rows * ceil(S / rows)`` is the padded
    population size (padding lanes carry a 0-step budget)."""
    n = max(1, int(n_devices))
    s = max(1, int(n_survivors))
    for rows in sorted((d for d in range(1, n + 1) if n % d == 0),
                       reverse=True):
        per = -(-s // rows)
        if rows <= s and (rows - 1) * per < s:
            return rows, n // rows, rows * per
    return 1, n, s  # unreachable: rows=1 always satisfies the invariant


def place_two_level(pstate: PopState, tc: TrainConfig, mesh: Mesh,
                    axis: str = "pop") -> PopState:
    """``device_put`` a population state onto a two-level ``(pop, model)``
    mesh: the lane axis spreads over ``axis`` and each lane's parameter /
    optimizer leaves shard over its own device row through the per-leaf
    composed specs (``two_level_state_specs`` x ``train_state_specs``).

    The width rules are the *module-coherent* ``tp_width_rules`` — the same
    partitioning the tensor-parallel step computes on — so a regrid onto a
    wider mesh genuinely re-partitions survivor state (optimizer memory per
    device drops ~1/W) instead of replicating it."""
    return jax.device_put(pstate, _two_level_layout(pstate, tc, mesh, axis))


def _two_level_layout(pstate, tc: TrainConfig, mesh: Mesh, axis: str):
    width = _mesh_width(mesh, axis)
    rules = tp_width_rules(tc.model, width) if width > 1 else None
    return two_level_state_specs(
        pstate, _state_logical_specs(tc), mesh, axis=axis, rules=rules)


def regrid_population_state(
    pstate: PopState,
    survivors,
    tc: TrainConfig,
    mesh: Optional[Mesh] = None,
    axis: str = "pop",
    pad_to: Optional[int] = None,
) -> PopState:
    """Gather ``survivors`` into a compact K' population and (optionally)
    re-lay it out on a new two-level mesh.

    The gather is the compiled ``regrid`` lane op (cached like every other
    lifecycle op); ``pad_to`` pads the survivor list to a fixed K' by
    repeating the first survivor (padding copies get 0-step budgets from the
    caller's hparam restack, so they freeze immediately and their scores are
    never read).  With ``mesh`` the compact state is ``device_put`` onto the
    new lane-row layout — resharding changes layout, never math."""
    k = int(pstate["diverged"].shape[0])
    idx = [int(i) for i in survivors]
    k2 = max(int(pad_to) if pad_to else len(idx), 1)
    idx = (idx + [idx[0] if idx else 0] * k2)[:k2]
    fn = get_compiled_lane_op(tc, k, "regrid")
    compact = fn(pstate, jnp.asarray(idx, jnp.int32))
    if mesh is not None:
        compact = place_two_level(compact, tc, mesh, axis=axis)
    return compact


def make_sharded_lane_init(tc: TrainConfig, mesh: Mesh, axis: str = "pop") -> Callable:
    """Lane reset with the K axis split over ``mesh`` (mirrors the sharded
    population step): each device re-inits only its own K/N block of lanes."""
    from jax import shard_map

    reset = make_lane_init(tc)
    pop = PartitionSpec(axis)
    return shard_map(reset, mesh=mesh, in_specs=(pop, pop, pop), out_specs=pop)


make_sharded_reset_lanes = make_sharded_lane_init


def make_sharded_lane_clone(tc: TrainConfig, mesh: Mesh, axis: str = "pop") -> Callable:
    """Donor clone with the K axis split over ``mesh``.

    ``donor_idx`` holds *global* lane ids, so a clone may cross a mesh
    boundary.  Instead of ``all_gather``-ing the population axis (which
    materializes the full K-lane state on every device — O(K) peak memory for
    a copy that only ever needs one lane), the donor states travel
    **point-to-point around a ring of ``ppermute``s**: round ``r`` rotates
    each device's K/N lane block one hop, and a device whose donor lives
    ``r`` hops upstream selects its donor's lane out of the passing block.
    Peak extra memory is ONE block (K/N lanes) regardless of mesh size, total
    wire traffic is the same N-1 blocks the gather moved, and the copied
    values are bit-identical to the vmapped clone's.
    """
    from jax import shard_map

    n = int(mesh.shape[axis])

    def clone(pstate: PopState, mask: jax.Array, donor_idx: jax.Array) -> PopState:
        blk = pstate["diverged"].shape[0]  # local lanes per device
        me = jax.lax.axis_index(axis)
        owner = donor_idx // blk           # device holding each lane's donor
        local = donor_idx % blk            # donor's index inside that block
        take = lambda t: jax.tree.map(lambda x: jnp.take(x, local, axis=0), t)
        perm = [(i, (i + 1) % n) for i in range(n)]

        buf = pstate                       # after r hops: block of device me-r
        donated = take(buf)                # r = 0: donors on this device
        for r in range(1, n):
            buf = jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), buf)
            src = (me - r) % n
            cand = take(buf)
            donated = jax.tree.map(
                lambda d, c: _per_trial(owner == src, c, d), donated, cand)

        inner = jax.tree.map(
            lambda d, o: _per_trial(mask, d, o), donated["inner"], pstate["inner"]
        )
        return {
            "inner": inner,
            "diverged": jnp.where(mask, donated["diverged"], pstate["diverged"]),
            "last_loss": jnp.where(mask, donated["last_loss"], pstate["last_loss"]),
        }

    pop = PartitionSpec(axis)
    return shard_map(clone, mesh=mesh, in_specs=(pop, pop, pop), out_specs=pop)


def make_sharded_lane_splice(tc: TrainConfig, mesh: Mesh, axis: str = "pop") -> Callable:
    """Single-lane splice with the K axis split over ``mesh``.

    ``lane`` is a global id; every device runs the (cheap, replicated) fresh
    init but only the owner of the target lane writes it into its local
    block — the rest keep their block bit-identical.
    """
    from jax import shard_map

    def splice(pstate: PopState, lane: jax.Array, key: jax.Array) -> PopState:
        blk = pstate["diverged"].shape[0]  # local lanes per device
        off = jax.lax.axis_index(axis) * blk
        local = jnp.clip(lane - off, 0, blk - 1)
        owns = (lane >= off) & (lane < off + blk)
        fresh = init_train_state(key, tc)

        def upd(o, f):
            new = jax.lax.dynamic_update_index_in_dim(o, f.astype(o.dtype), local, 0)
            return jnp.where(owns, new, o)

        inner = jax.tree.map(upd, pstate["inner"], fresh)
        div = jax.lax.dynamic_update_index_in_dim(
            pstate["diverged"], jnp.asarray(False), local, 0
        )
        last = jax.lax.dynamic_update_index_in_dim(
            pstate["last_loss"], jnp.float32(jnp.inf), local, 0
        )
        return {
            "inner": inner,
            "diverged": jnp.where(owns, div, pstate["diverged"]),
            "last_loss": jnp.where(owns, last, pstate["last_loss"]),
        }

    pop = PartitionSpec(axis)
    return shard_map(
        splice, mesh=mesh,
        in_specs=(pop, PartitionSpec(), PartitionSpec()),
        out_specs=pop,
    )


def make_sharded_lane_snapshot(tc: TrainConfig, mesh: Mesh, axis: str = "pop") -> Callable:
    """Single-lane snapshot with the K axis split over ``mesh``.

    ``lane`` is a global id.  The owning device indexes the lane out of its
    local block; every other device contributes zeros, and a ``psum`` over
    the population axis replicates the harvested lane state to all devices
    (the output carries no lane axis, so it cannot be partitioned on one) —
    peak extra memory is one lane, never a gather of the population.  Bool
    leaves ride the sum as int32 (a masked sum of one contribution, so the
    round-trip is exact).
    """
    from jax import shard_map

    def snapshot(pstate: PopState, lane: jax.Array):
        blk = pstate["diverged"].shape[0]  # local lanes per device
        off = jax.lax.axis_index(axis) * blk
        local = jnp.clip(lane - off, 0, blk - 1)
        owns = (lane >= off) & (lane < off + blk)

        def harvest(x):
            v = jax.lax.dynamic_index_in_dim(x, local, 0, keepdims=False)
            summed = jax.lax.psum(
                jnp.where(owns, v.astype(jnp.int32), 0) if v.dtype == jnp.bool_
                else jnp.where(owns, v, jnp.zeros_like(v)),
                axis,
            )
            return summed.astype(bool) if v.dtype == jnp.bool_ else summed

        return {
            "inner": jax.tree.map(harvest, pstate["inner"]),
            "diverged": harvest(pstate["diverged"]),
            "last_loss": harvest(pstate["last_loss"]),
        }

    pop = PartitionSpec(axis)
    return shard_map(
        snapshot, mesh=mesh,
        in_specs=(pop, PartitionSpec()),
        out_specs=PartitionSpec(),  # replicated: the one harvested lane
    )


def make_sharded_lane_restore(tc: TrainConfig, mesh: Mesh, axis: str = "pop") -> Callable:
    """Snapshot restore with the K axis split over ``mesh``.

    ``lane`` is a global id and ``snap`` is replicated; only the owner of the
    target lane writes the snapshot into its local block (mirrors the sharded
    splice), so the other devices' blocks stay bit-identical.
    """
    from jax import shard_map

    def restore(pstate: PopState, lane: jax.Array, snap) -> PopState:
        blk = pstate["diverged"].shape[0]
        off = jax.lax.axis_index(axis) * blk
        local = jnp.clip(lane - off, 0, blk - 1)
        owns = (lane >= off) & (lane < off + blk)

        def put(o, f):
            new = jax.lax.dynamic_update_index_in_dim(o, f.astype(o.dtype), local, 0)
            return jnp.where(owns, new, o)

        return {
            "inner": jax.tree.map(put, pstate["inner"], snap["inner"]),
            "diverged": put(pstate["diverged"], snap["diverged"]),
            "last_loss": put(pstate["last_loss"], snap["last_loss"]),
        }

    pop = PartitionSpec(axis)
    return shard_map(
        restore, mesh=mesh,
        in_specs=(pop, PartitionSpec(), PartitionSpec()),
        out_specs=pop,
    )


# -- fused multi-step scan (chunked execution) ----------------------------------
#
# The per-step drivers pay one host dispatch AND one host-built batch per
# training step.  ``make_population_scan_step`` fuses T steps into ONE device
# program: a ``jax.lax.scan`` over the population step whose batches are
# synthesized *inside* the scan from per-lane stream words and a traced step
# counter (``repro.data.pipeline.synth_population_batch`` — bit-identical to
# the host's ``make_batch`` by construction, so the fused engine reproduces
# the per-step loop exactly).  The host only re-enters at *event* steps
# (rung boundaries, retirements, PBT rounds, the divergence poll), so chunk
# boundaries are aligned to events by the drivers and T host dispatches
# collapse to one per chunk.


def make_population_scan_step(
    tc: TrainConfig, data, chunk: int, per_trial_batch: bool = True
) -> Callable:
    """``(pstate, hp, steps0, stream_lo, stream_hi) -> (pstate, metrics)``
    advancing every lane ``chunk`` steps in one program.

    ``data`` is the ``SyntheticLM`` stream spec (baked in — the compiled
    program *is* the data pipeline for these lanes); ``steps0`` is each
    lane's data cursor at the chunk start (int32[K], or a scalar in
    shared-stream mode) and ``stream_lo``/``stream_hi`` are the per-lane
    stream words from ``split_streams`` (uint32[K], scalars in shared-stream
    mode).  Step ``t`` of the chunk consumes exactly the batch the host loop
    would build at cursor ``steps0 + t``; budget/divergence masking is the
    ordinary population-step semantics, so a lane whose budget ends (or that
    diverges) mid-chunk freezes in place and the chunk remains safe to run
    past it.  ``metrics`` come back stacked with a leading ``(chunk,)`` axis.
    """
    from ..data.pipeline import synth_population_batch, synth_tokens, tokens_to_batch

    step = make_population_train_step(tc, per_trial_batch=per_trial_batch)

    def scan_chunk(pstate: PopState, hp: HParams, steps0, stream_lo, stream_hi):
        def body(carry, t):
            if per_trial_batch:
                batch = synth_population_batch(
                    data, stream_lo, stream_hi, steps0 + t, xp=jnp)
            else:
                toks = synth_tokens(
                    jnp, data, (data.global_batch,), steps0 + t,
                    stream_lo, stream_hi)
                batch = tokens_to_batch(jnp, data, toks)
            new, metrics = step(carry, batch, hp)
            return new, metrics

        return jax.lax.scan(
            body, pstate, jnp.arange(int(chunk), dtype=jnp.int32))

    return scan_chunk


def make_sharded_population_scan_step(
    tc: TrainConfig,
    mesh: Mesh,
    data,
    chunk: int,
    per_trial_batch: bool = True,
    axis: str = "pop",
) -> Callable:
    """``shard_map`` twin of the fused scan: each device runs the T-step scan
    over its own K/N lane block, synthesizing only its own lanes' batches on
    device.  Stacked metrics come back partitioned on their lane axis
    (leading axis is the chunk).

    On a two-level mesh each lane row's scan is width-W tensor parallel (see
    ``make_sharded_population_step``); the in-scan batch synthesis replicates
    across the row (same lanes, same streams), which is exactly the TP batch
    contract."""
    from jax import shard_map

    fn = make_population_scan_step(
        tc, data, chunk, per_trial_batch=per_trial_batch)
    pop = PartitionSpec(axis)
    rep = PartitionSpec()
    lane = pop if per_trial_batch else rep
    width = _mesh_width(mesh, axis)
    if width > 1:
        state_ps, _ = _tp_state_pspecs(tc, mesh, axis)
        return shard_map(
            _tp_body(fn, tc, width),
            mesh=mesh,
            in_specs=(state_ps, pop, lane, lane, lane),
            out_specs=(state_ps, PartitionSpec(None, axis)),
            check_vma=False,
        )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(pop, pop, lane, lane, lane),
        out_specs=(pop, PartitionSpec(None, axis)),
        check_vma=not _fused_kernels_on(tc),
    )


def make_population_ring_scan_step(
    tc: TrainConfig, data, chunk: int, capacity: int
) -> Callable:
    """``(pstate, hp, ring, slot0) -> (pstate, metrics)``: the fused scan fed
    from a device-resident prefetch ring instead of in-scan synthesis.

    ``ring`` is the ``repro.data.ring.PrefetchRing`` device array —
    ``(capacity, K, batch, seq_len+1)`` int32 token slabs, one slab per
    global step, host-filled ahead of the scan.  Step ``t`` of the chunk
    reads slot ``(slot0 + t) % capacity`` with ``lax.dynamic_index_in_dim``
    (``slot0`` is the dispatch step's slot, traced so one program serves
    every ring phase) and splits it into the batch dict on device; the train
    step itself — budget/divergence masking included — is identical to the
    in-scan-synth path, so a ring filled by the host synth adapter reproduces
    that engine bit-for-bit.  The ring argument is read-only: only the
    population state donates.
    """
    from ..data.pipeline import tokens_to_batch

    step = make_population_train_step(tc, per_trial_batch=True)
    cap = int(capacity)

    def scan_chunk(pstate: PopState, hp: HParams, ring, slot0):
        def body(carry, t):
            slab = jax.lax.dynamic_index_in_dim(
                ring, (slot0 + t) % cap, 0, keepdims=False)
            batch = tokens_to_batch(jnp, data, slab)
            new, metrics = step(carry, batch, hp)
            return new, metrics

        return jax.lax.scan(
            body, pstate, jnp.arange(int(chunk), dtype=jnp.int32))

    return scan_chunk


def make_sharded_population_ring_scan_step(
    tc: TrainConfig,
    mesh: Mesh,
    data,
    chunk: int,
    capacity: int,
    axis: str = "pop",
) -> Callable:
    """``shard_map`` twin of the ring scan: the ring's lane axis is placed on
    the ``pop`` mesh axis, so each device scans over its own K/N lane block
    reading only its own lanes' slabs (the host fill ``device_put``s slabs
    with the same sharding — no gather)."""
    from jax import shard_map

    fn = make_population_ring_scan_step(tc, data, chunk, capacity)
    pop = PartitionSpec(axis)
    width = _mesh_width(mesh, axis)
    if width > 1:
        state_ps, _ = _tp_state_pspecs(tc, mesh, axis)
        return shard_map(
            _tp_body(fn, tc, width),
            mesh=mesh,
            in_specs=(state_ps, pop, PartitionSpec(None, axis), PartitionSpec()),
            out_specs=(state_ps, PartitionSpec(None, axis)),
            check_vma=False,
        )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(pop, pop, PartitionSpec(None, axis), PartitionSpec()),
        out_specs=(pop, PartitionSpec(None, axis)),
        check_vma=not _fused_kernels_on(tc),
    )


def make_sharded_population_step(
    tc: TrainConfig,
    mesh: Mesh,
    per_trial_batch: bool = False,
    axis: str = "pop",
) -> Callable:
    """Population step with the K axis split over ``mesh``'s ``axis``.

    Wraps the vmapped step in ``shard_map``: each of the N devices advances a
    contiguous K/N block of trials, every argument/output with a leading K
    axis is partitioned on ``axis``, and the (shared-stream) batch replicates.
    K must be divisible by N — ``pad_population`` gives the padded size and
    callers top up with 0-budget trials that freeze immediately.

    On a two-level ``(pop, model)`` mesh the step shard_maps over BOTH axes:
    each lane row runs a width-W tensor-parallel program (heads / ff / mamba
    channels width-local per ``tp_width_rules``, psums at the model-code
    seams), so the model axis carries compute instead of replicas.
    """
    from jax import shard_map

    step = make_population_train_step(tc, per_trial_batch=per_trial_batch)
    pop = PartitionSpec(axis)
    batch_spec = pop if per_trial_batch else PartitionSpec()
    width = _mesh_width(mesh, axis)
    if width > 1:
        state_ps, _ = _tp_state_pspecs(tc, mesh, axis)
        # check_vma=False: activations/metrics ARE replicated across each lane
        # row (the seam psums make them so), but the static replication
        # checker cannot see through custom_vjp seams
        return shard_map(
            _tp_body(step, tc, width),
            mesh=mesh,
            in_specs=(state_ps, batch_spec, pop),
            out_specs=(state_ps, pop),
            check_vma=False,
        )
    return shard_map(
        step,
        mesh=mesh,
        in_specs=(pop, batch_spec, pop),
        out_specs=(pop, pop),
        check_vma=not _fused_kernels_on(tc),
    )


# -- device-side decision rules -------------------------------------------------
#
# The fused scan above still returns to the host at every *event* step (rung
# boundary, retirement, PBT round), which caps the chunk length at the event
# gap.  The rule-carrying scan below removes that cap: the early-stop rung
# rules (``repro.core.proposer.early_stop``) and the PBT sliding-window
# quantile are re-expressed as pure vectorized functions of scan-carried
# state, evaluated after every fused step.  A lane whose budget a rule
# truncates freezes at the very next step *inside* the scan (its traced
# ``total_steps`` is rebuilt from the carried budgets each step), so a whole
# ASHA ladder runs as ONE dispatch and the host only harvests retirements
# from the emitted per-step budget log afterwards.
#
# Rule-state layout (a flat dict carried through the scan next to the
# population state; per-lane leaves shard on the population axis, history /
# window leaves replicate):
#
#   common      budgets f32[K] (lane-local step budget; absolute in the
#               batch driver where base == 0), base f32[K] (each lane's
#               applied-step offset: ``total_steps = base + budgets``),
#               local0 i32[K] (lane-local wall step at chunk start)
#   cohort      boundaries f32[B], eta f32[] — the synchronized-flight rule
#               (``InFlightSuccessiveHalving.__call__``)
#   staggered   boundaries, eta, hist f32[B, C] (+inf padded per-rung loss
#               history), counts i32[B] — the asynchronous-SHA rule
#               (``InFlightSuccessiveHalving.observe``)
#   pbt         quantile f32[], wscore f32[W] (score ring), wcount i32[],
#               vbottom/vready bool[K], vlo/vhi f32[K] — the sliding-window
#               quantile; verdicts latch per lane at its round-end step
#               (``PBTLifecycle.decide`` consumes them on the host)


def cohort_rule_update(rules, losses, diverged, local):
    """In-scan twin of ``InFlightSuccessiveHalving.__call__``.

    ``local`` is the cohort's wall step (i32[K], all lanes equal — the batch
    driver's synchronized flights).  A no-op except at rung boundaries, where
    diverged lanes' dead budgets are reclaimed and ranked lanes below the
    ``1/eta`` cut are truncated to the boundary step.  The O(K^2) pairwise
    rank reproduces ``np.argsort``'s stable ascending order (ties keep the
    lower lane index first), so the cut set is bit-identical to the host
    rule's.
    """
    budgets = rules["budgets"]
    boundaries = rules["boundaries"]
    eta = rules["eta"]
    k = budgets.shape[0]
    idx = jnp.arange(k)
    sf = local[0].astype(jnp.float32)
    at = jnp.any(boundaries == sf)
    dead = diverged & (budgets > sf)
    b2 = jnp.where(dead, sf, budgets)
    ranked = (b2 >= sf) & (b2 > 0) & ~diverged & jnp.isfinite(losses)
    n_ranked = ranked.sum()
    n_keep = jnp.ceil(n_ranked.astype(jnp.float32) / eta).astype(jnp.int32)
    lower = ((losses[None, :] < losses[:, None]) & ranked[None, :]).sum(1)
    ties = (
        (losses[None, :] == losses[:, None]) & ranked[None, :]
        & (idx[None, :] < idx[:, None])
    ).sum(1)
    rank = lower + ties
    noop = (n_ranked <= 1) | (n_keep >= n_ranked)
    cut = ranked & (rank >= n_keep) & (b2 > sf) & ~noop
    nb = jnp.where(cut, sf, b2)
    return dict(rules, budgets=jnp.where(at, nb, budgets))


def staggered_rule_update(rules, losses, diverged, local):
    """In-scan twin of ``InFlightSuccessiveHalving.observe`` (async SHA).

    ``local`` is each lane's own wall step (i32[K]) — refilled lanes sit at
    different steps.  A lane whose local step lands on a rung boundary (and
    that is live, finite and still inside its budget — a frozen lane's wall
    clock keeps ticking past retirement inside a long chunk) appends its loss
    to that rung's history and is truncated unless it ranks in the top
    ``1/eta`` of the history *including* its own entry.  Simultaneous hits
    append in lane order, reproducing the host rule's lane loop exactly.
    ``hist`` capacity must cover every possible append (the driver sizes it
    as current max count + K before each dispatch).
    """
    budgets = rules["budgets"]
    boundaries = rules["boundaries"]
    eta = rules["eta"]
    hist = rules["hist"]
    counts = rules["counts"]
    k = budgets.shape[0]
    n_rungs, cap = hist.shape
    idx = jnp.arange(k)
    lf = local.astype(jnp.float32)
    eq = lf[:, None] == boundaries[None, :]                      # [K, B]
    at = eq.any(1)
    bi = jnp.argmax(eq, 1)
    hit = (
        at & (budgets > 0) & ~diverged
        & jnp.isfinite(losses) & (lf <= budgets)
    )
    j_lt_i = idx[None, :] < idx[:, None]
    same = hit[None, :] & hit[:, None] & (bi[None, :] == bi[:, None])
    n_before = (same & j_lt_i).sum(1)
    new_len = counts[bi] + n_before + 1
    n_keep = jnp.ceil(new_len.astype(jnp.float32) / eta).astype(jnp.int32)
    col = jnp.arange(cap)
    rank_hist = (
        (hist[bi] < losses[:, None]) & (col[None, :] < counts[bi][:, None])
    ).sum(1)
    rank_same = (same & j_lt_i & (losses[None, :] < losses[:, None])).sum(1)
    rank = rank_hist + rank_same
    cut = hit & (rank >= n_keep) & (budgets > lf)
    new_budgets = jnp.where(cut, lf, budgets)
    slot = counts[bi] + n_before
    ok = hit & (slot < cap)
    flat = jnp.where(ok, bi * cap + slot, n_rungs * cap)         # last = dump
    padded = jnp.concatenate([hist.reshape(-1), jnp.zeros((1,), hist.dtype)])
    new_hist = padded.at[flat].set(losses)[: n_rungs * cap].reshape(n_rungs, cap)
    new_counts = counts + (ok[:, None] & eq).sum(0)
    return dict(rules, budgets=new_budgets, hist=new_hist, counts=new_counts)


def pbt_rule_update(rules, losses, diverged, local):
    """In-scan PBT sliding-window quantile (``PBTLifecycle``'s async rule).

    A lane hitting its round-end step (``local == budgets``; a diverged
    lane's wall clock still reaches it) appends its score to the ring window
    in lane order, then latches a per-lane verdict: ``vbottom`` (score at or
    below the low quantile of the updated window), the quantile values
    ``vlo``/``vhi``, and ``vready``.  The host harvest feeds the verdicts to
    ``PBTLifecycle.note_device_verdict`` — donor choice and hyperparameter
    perturbation stay host-side (they draw from the proposer's RNG).
    Budgets are never truncated here: PBT rounds end by budget.
    """
    budgets = rules["budgets"]
    wscore = rules["wscore"]
    wcount = rules["wcount"]
    quantile = rules["quantile"]
    from ..core.proposer.pbt import DIVERGED_SCORE, window_quantile

    k = budgets.shape[0]
    w = wscore.shape[0]
    idx = jnp.arange(k)
    lf = local.astype(jnp.float32)
    hit = (budgets > 0) & (lf == budgets)
    score = jnp.where(
        diverged | ~jnp.isfinite(losses), jnp.float32(DIVERGED_SCORE), -losses
    )
    n_before = (hit[None, :] & (idx[None, :] < idx[:, None])).sum(1)
    slot = (wcount + n_before) % w
    flat = jnp.where(hit, slot, w)                               # last = dump
    padded = jnp.concatenate([wscore, jnp.zeros((1,), wscore.dtype)])
    new_wscore = padded.at[flat].set(score)[:w]
    new_wcount = wcount + hit.sum()
    lo, hi = window_quantile(new_wscore, new_wcount, quantile, xp=jnp)
    return dict(
        rules,
        wscore=new_wscore,
        wcount=new_wcount,
        vbottom=jnp.where(hit, score <= lo, rules["vbottom"]),
        vready=rules["vready"] | hit,
        vlo=jnp.where(hit, lo, rules["vlo"]),
        vhi=jnp.where(hit, hi, rules["vhi"]),
    )


_RULE_UPDATES: Dict[str, Callable] = {
    "cohort": cohort_rule_update,
    "staggered": staggered_rule_update,
    "pbt": pbt_rule_update,
}
# per-lane rule-state leaves (shard on the population axis; the rest replicate)
_RULE_LANE_KEYS: Dict[str, frozenset] = {
    "cohort": frozenset({"budgets", "base", "local0"}),
    "staggered": frozenset({"budgets", "base", "local0"}),
    "pbt": frozenset({"budgets", "base", "local0",
                      "vbottom", "vready", "vlo", "vhi"}),
}


def cohort_rule_state(budgets, base, local0, boundaries, eta) -> Dict[str, Any]:
    return {
        "budgets": jnp.asarray(budgets, jnp.float32),
        "base": jnp.asarray(base, jnp.float32),
        "local0": jnp.asarray(local0, jnp.int32),
        "boundaries": jnp.asarray(boundaries, jnp.float32),
        "eta": jnp.asarray(eta, jnp.float32),
    }


def staggered_rule_state(
    budgets, base, local0, boundaries, eta, hist, counts
) -> Dict[str, Any]:
    state = cohort_rule_state(budgets, base, local0, boundaries, eta)
    state["hist"] = jnp.asarray(hist, jnp.float32)
    state["counts"] = jnp.asarray(counts, jnp.int32)
    return state


def pbt_rule_state(
    budgets, base, local0, quantile, wscore, wcount
) -> Dict[str, Any]:
    budgets = jnp.asarray(budgets, jnp.float32)
    k = budgets.shape[0]
    return {
        "budgets": budgets,
        "base": jnp.asarray(base, jnp.float32),
        "local0": jnp.asarray(local0, jnp.int32),
        "quantile": jnp.asarray(quantile, jnp.float32),
        "wscore": jnp.asarray(wscore, jnp.float32),
        "wcount": jnp.asarray(wcount, jnp.int32),
        "vbottom": jnp.zeros((k,), bool),
        "vready": jnp.zeros((k,), bool),
        "vlo": jnp.zeros((k,), jnp.float32),
        "vhi": jnp.zeros((k,), jnp.float32),
    }


def rule_state_specs(mode: str, axis: str = "pop") -> Dict[str, PartitionSpec]:
    """PartitionSpecs for a rule-state dict on the population mesh."""
    pop = PartitionSpec(axis)
    rep = PartitionSpec()
    lane_keys = _RULE_LANE_KEYS[mode]
    keys = {"budgets", "base", "local0"}
    if mode in ("cohort", "staggered"):
        keys |= {"boundaries", "eta"}
    if mode == "staggered":
        keys |= {"hist", "counts"}
    if mode == "pbt":
        keys |= {"quantile", "wscore", "wcount", "vbottom", "vready", "vlo", "vhi"}
    return {k: (pop if k in lane_keys else rep) for k in keys}


def _sharded_rule_update(mode: str, axis: str) -> Callable:
    """Wrap a rule update for a sharded scan: gather the K-length lane
    vectors (never the train state), evaluate the global rule identically on
    every device, and slice each device's lane block back out.  History /
    window / config leaves are replicated, so the global computation keeps
    them consistent across devices by construction."""
    update = _RULE_UPDATES[mode]
    lane_keys = _RULE_LANE_KEYS[mode]

    def upd(rules, losses, diverged, local):
        blk = losses.shape[0]
        me = jax.lax.axis_index(axis)
        gather = lambda x: jax.lax.all_gather(x, axis, tiled=True)
        grules = {k: (gather(v) if k in lane_keys else v) for k, v in rules.items()}
        gnew = update(grules, gather(losses), gather(diverged), gather(local))
        return {
            k: (jax.lax.dynamic_slice_in_dim(v, me * blk, blk)
                if k in lane_keys else v)
            for k, v in gnew.items()
        }

    return upd


def make_population_rule_scan_step(
    tc: TrainConfig,
    data,
    chunk: int,
    mode: str,
    per_trial_batch: bool = True,
    rule_update: Optional[Callable] = None,
) -> Callable:
    """``(pstate, hp, steps0, stream_lo, stream_hi, rules)
    -> ((pstate, rules), metrics)`` — the fused scan with an in-scan
    decision rule.

    Like ``make_population_scan_step`` but each step rebuilds the traced
    ``hp.total_steps`` from the carried rule state (``base + budgets``) and
    then applies ``mode``'s rule update to the post-step losses, so a rung
    cut (or PBT verdict) lands at exactly the step the host loop would have
    applied it — without leaving the device.  ``metrics`` gains a
    ``budgets`` log (``[chunk, K]``): the emitted event trace the host
    harvests retirements from.
    """
    from ..data.pipeline import synth_population_batch, synth_tokens, tokens_to_batch

    step = make_population_train_step(tc, per_trial_batch=per_trial_batch)
    update = _RULE_UPDATES[mode] if rule_update is None else rule_update

    def scan_chunk(pstate: PopState, hp: HParams, steps0, stream_lo, stream_hi,
                   rules):
        def body(carry, t):
            pst, rl = carry
            hp_t = dataclasses.replace(hp, total_steps=rl["base"] + rl["budgets"])
            if per_trial_batch:
                batch = synth_population_batch(
                    data, stream_lo, stream_hi, steps0 + t, xp=jnp)
            else:
                toks = synth_tokens(
                    jnp, data, (data.global_batch,), steps0 + t,
                    stream_lo, stream_hi)
                batch = tokens_to_batch(jnp, data, toks)
            new, metrics = step(pst, batch, hp_t)
            local = rl["local0"] + t + 1
            new_rl = update(rl, new["last_loss"], new["diverged"], local)
            return (new, new_rl), dict(metrics, budgets=new_rl["budgets"])

        return jax.lax.scan(
            body, (pstate, rules), jnp.arange(int(chunk), dtype=jnp.int32))

    return scan_chunk


def make_sharded_population_rule_scan_step(
    tc: TrainConfig,
    mesh: Mesh,
    data,
    chunk: int,
    mode: str,
    per_trial_batch: bool = True,
    axis: str = "pop",
) -> Callable:
    """``shard_map`` twin of the rule-carrying scan.

    Training stays embarrassingly parallel (each device scans its own K/N
    lane block), but the decision rules are *global*: at each step the
    K-length loss/budget/latch vectors are ``all_gather``-ed (never the
    train state), every device evaluates the identical global rule, and each
    slices its own block of the new budgets back out — so the sharded cut
    set is bit-identical to the vmapped engine's by construction.
    """
    from jax import shard_map

    fn = make_population_rule_scan_step(
        tc, data, chunk, mode, per_trial_batch=per_trial_batch,
        rule_update=_sharded_rule_update(mode, axis),
    )
    pop = PartitionSpec(axis)
    rep = PartitionSpec()
    lane = pop if per_trial_batch else rep
    rules_spec = rule_state_specs(mode, axis)
    # check_vma=False: the history/window leaves ARE replicated (every device
    # runs the identical global update on all_gather-ed inputs), but the
    # static replication checker cannot infer that through the gather
    width = _mesh_width(mesh, axis)
    if width > 1:
        # two-level mesh: training is width-W tensor parallel per lane row;
        # the rule update still all_gathers over the pop axis only — devices
        # in one row hold identical (replicated) losses, so every device
        # evaluates the same global rule and the cut set stays width-invariant
        state_ps, _ = _tp_state_pspecs(tc, mesh, axis)
        return shard_map(
            _tp_body(fn, tc, width),
            mesh=mesh,
            in_specs=(state_ps, pop, lane, lane, lane, rules_spec),
            out_specs=((state_ps, rules_spec), PartitionSpec(None, axis)),
            check_vma=False,
        )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(pop, pop, lane, lane, lane, rules_spec),
        out_specs=((pop, rules_spec), PartitionSpec(None, axis)),
        check_vma=False,
    )


def pad_population(k: int, mesh: Optional[Mesh], axis: str = "pop") -> int:
    """Smallest population size >= k that divides evenly over ``mesh``'s lane
    rows (on a two-level mesh that is the pop-axis size, NOT the device
    count: a width-W row serves ONE lane block W-wide)."""
    n = 1 if mesh is None else _pop_rows(mesh, axis)
    return ((max(k, 1) + n - 1) // n) * n


def _population_layout(pstate, mesh: Mesh, axis: str,
                       tc: Optional[TrainConfig]):
    """Per-leaf shardings of a population state (or its shapes) on ``mesh``:
    lanes over ``axis``; on a two-level mesh with ``tc``, each lane's
    leaves width-partitioned per ``tp_width_rules``."""
    if tc is not None and _mesh_width(mesh, axis) > 1:
        return _two_level_layout(pstate, tc, mesh, axis)
    return population_specs(pstate, mesh, axis)


def shard_population_state(
    pstate: PopState, mesh: Mesh, axis: str = "pop",
    tc: Optional[TrainConfig] = None,
) -> PopState:
    """Place a freshly initialized population state on the mesh (leading K dim
    on ``axis``) so the first sharded step does not pay an input reshard.
    On a two-level mesh pass ``tc`` so each lane's parameter/optimizer leaves
    land width-partitioned per ``tp_width_rules`` (matching what the TP step
    computes on) instead of row-replicated."""
    return jax.device_put(pstate, _population_layout(pstate, mesh, axis, tc))


def init_population_state_on_mesh(
    key, tc: TrainConfig, population: int, mesh: Mesh, axis: str = "pop",
) -> PopState:
    """``init_population_state`` laid straight onto ``mesh``, in the layout
    ``shard_population_state(..., tc=tc)`` gives.

    The one template lane is initialized as on a single device (the same
    numerics); the K-fold broadcast runs under ``jit`` with the population
    layout as ``out_shardings``, so every device materializes only its own
    lanes.  Broadcasting on one device first would hold the whole
    population there — at published widths, more than one chip's memory."""
    out = _population_layout(
        _population_state_shapes(tc, population), mesh, axis, tc)
    return jax.jit(lambda one: _broadcast_lanes(one, population),
                   out_shardings=out)(init_train_state(key, tc))


# -- compile-once caches --------------------------------------------------------
#
# vmapped: one entry per (static config, population size, batch mode);
# sharded: additionally keyed on the mesh's device set and axis name.

_POP_CACHE: Dict[Tuple, Any] = {}
_POP_CACHE_LOCK = threading.Lock()


def get_compiled_population_step(
    tc: TrainConfig, population: int, per_trial_batch: bool = False
):
    """Memoized ``jax.jit`` of the population step with donated state."""
    key = (static_step_key(tc), int(population), bool(per_trial_batch))
    with _POP_CACHE_LOCK:
        fn = _POP_CACHE.get(key)
        if fn is None:
            fn = jax.jit(
                make_population_train_step(tc, per_trial_batch=per_trial_batch),
                donate_argnums=0,
            )
            _POP_CACHE[key] = fn
    return fn


def get_compiled_sharded_population_step(
    tc: TrainConfig,
    population: int,
    mesh: Optional[Mesh] = None,
    per_trial_batch: bool = False,
    axis: str = "pop",
):
    """Memoized jitted ``shard_map`` population step over ``mesh`` (default: a
    1-D mesh over every local device).  Raises if K does not divide over the
    mesh — pad with ``pad_population`` first."""
    mesh = mesh if mesh is not None else population_mesh(axis=axis)
    _check_rows(population, mesh, axis)
    key = (
        static_step_key(tc), int(population), bool(per_trial_batch),
    ) + _mesh_cache_key(mesh, axis)
    with _POP_CACHE_LOCK:
        fn = _POP_CACHE.get(key)
        if fn is None:
            fn = jax.jit(
                make_sharded_population_step(
                    tc, mesh, per_trial_batch=per_trial_batch, axis=axis
                ),
                donate_argnums=0,
            )
            _POP_CACHE[key] = fn
    return fn


def get_compiled_population_scan_step(
    tc: TrainConfig,
    population: int,
    data,
    chunk: int,
    mesh: Optional[Mesh] = None,
    per_trial_batch: bool = True,
    axis: str = "pop",
):
    """Memoized jitted fused-scan step (optionally the ``shard_map`` twin).

    Keyed like the per-step programs plus the chunk length and the data
    stream spec (``data.spec_key`` — the program bakes the batch synthesis
    in).  Drivers dispatch power-of-two chunk sizes, so an experiment
    compiles at most ``log2(chunk_steps) + 1`` scan programs per engine.
    ``clear_population_cache()`` covers these entries too.
    """
    if mesh is not None:
        _check_rows(population, mesh, axis)
    key = (
        static_step_key(tc), int(population), bool(per_trial_batch),
        "scan", int(chunk), data.spec_key,
    ) + (_mesh_cache_key(mesh, axis) if mesh is not None else ())
    with _POP_CACHE_LOCK:
        fn = _POP_CACHE.get(key)
        if fn is None:
            if mesh is None:
                built = make_population_scan_step(
                    tc, data, chunk, per_trial_batch=per_trial_batch)
            else:
                built = make_sharded_population_scan_step(
                    tc, mesh, data, chunk,
                    per_trial_batch=per_trial_batch, axis=axis)
            fn = jax.jit(built, donate_argnums=0)
            _POP_CACHE[key] = fn
    return fn


def get_compiled_population_ring_scan_step(
    tc: TrainConfig,
    population: int,
    data,
    chunk: int,
    capacity: int,
    mesh: Optional[Mesh] = None,
    axis: str = "pop",
):
    """Memoized jitted ring-fed fused scan (``--data-ring``) — the seventh
    entry in the compiled-program family.

    Keyed like the in-scan-synth programs plus the ring capacity (the slot
    modulus is baked in) under the ``"ringscan"`` marker.  Only the
    population state donates — the ring buffer is owned and rotated by the
    fill thread, never by the scan.
    """
    if mesh is not None:
        _check_rows(population, mesh, axis)
    key = (
        static_step_key(tc), int(population), "ringscan", int(chunk),
        int(capacity), data.spec_key,
    ) + (_mesh_cache_key(mesh, axis) if mesh is not None else ())
    with _POP_CACHE_LOCK:
        fn = _POP_CACHE.get(key)
        if fn is None:
            if mesh is None:
                built = make_population_ring_scan_step(
                    tc, data, chunk, capacity)
            else:
                built = make_sharded_population_ring_scan_step(
                    tc, mesh, data, chunk, capacity, axis=axis)
            fn = jax.jit(built, donate_argnums=0)
            _POP_CACHE[key] = fn
    return fn


def get_compiled_population_rule_scan_step(
    tc: TrainConfig,
    population: int,
    data,
    chunk: int,
    mode: str,
    mesh: Optional[Mesh] = None,
    per_trial_batch: bool = True,
    axis: str = "pop",
):
    """Memoized jitted rule-carrying fused scan (``--device-rules``).

    Keyed like the plain scan programs plus the rule ``mode`` — the rule
    update is baked into the scan body.  The staggered mode's history
    capacity and the PBT mode's window length are *shapes* of the rules
    pytree, not part of the key: ``jax.jit`` specializes on them internally,
    and drivers size them to powers of two so an experiment compiles a
    bounded program set.
    """
    if mesh is not None:
        _check_rows(population, mesh, axis)
    key = (
        static_step_key(tc), int(population), bool(per_trial_batch),
        "rulescan", str(mode), int(chunk), data.spec_key,
    ) + (_mesh_cache_key(mesh, axis) if mesh is not None else ())
    with _POP_CACHE_LOCK:
        fn = _POP_CACHE.get(key)
        if fn is None:
            if mesh is None:
                built = make_population_rule_scan_step(
                    tc, data, chunk, mode, per_trial_batch=per_trial_batch)
            else:
                built = make_sharded_population_rule_scan_step(
                    tc, mesh, data, chunk, mode,
                    per_trial_batch=per_trial_batch, axis=axis)
            fn = jax.jit(built, donate_argnums=0)
            _POP_CACHE[key] = fn
    return fn


# one builder table for the lifecycle layer: op -> (vmapped, shard_map twin).
# "snapshot" is the one READ-ONLY op: it must not donate the population state
# (the flight keeps training on it after the harvest), so the jit wrapper
# below keys donation off this table too.
_LANE_OPS: Dict[str, Tuple[Callable, Callable]] = {
    "init": (make_lane_init, make_sharded_lane_init),
    "clone": (make_lane_clone, make_sharded_lane_clone),
    "splice": (make_lane_splice, make_sharded_lane_splice),
    "snapshot": (make_lane_snapshot, make_sharded_lane_snapshot),
    "restore": (make_lane_restore, make_sharded_lane_restore),
    "regrid": (make_lane_regrid, make_sharded_lane_regrid),
}
# snapshot reads the state the flight keeps training on; regrid's output has
# a different lane count than its input, so the buffers are never reusable —
# neither may donate.
_READONLY_LANE_OPS = frozenset({"snapshot", "regrid"})


def get_compiled_lane_op(
    tc: TrainConfig,
    population: int,
    op: str,
    mesh: Optional[Mesh] = None,
    axis: str = "pop",
):
    """Memoized ``jax.jit`` of a lane-lifecycle op.

    ``op`` is one of ``init`` / ``clone`` / ``splice`` / ``snapshot`` /
    ``restore`` / ``regrid``; with ``mesh`` the ``shard_map`` twin is compiled
    instead
    (keyed like the sharded population step, so a streaming flight compiles
    each op it uses exactly once).  Mutating ops donate the population state;
    ``snapshot`` reads it and leaves the flight state alive.

    On a two-level (width>1) mesh the hand-written shard_map twins do not
    apply — state leaves are width-partitioned per lane row, not merely
    lane-blocked — so the vmapped op runs under GSPMD with
    ``out_shardings`` pinned to the TP layout (``two_level_state_specs`` x
    ``tp_width_rules``).  Lifecycle ops fire at event boundaries, not every
    step, so letting XLA partition them costs nothing on the hot path and
    keeps them bit-identical to the vmapped originals by construction.
    """
    if op not in _LANE_OPS:
        raise KeyError(f"unknown lane op {op!r}; available: {sorted(_LANE_OPS)}")
    if mesh is not None:
        _check_rows(population, mesh, axis)
    key = (static_step_key(tc), int(population), f"lane-{op}") + (
        _mesh_cache_key(mesh, axis) if mesh is not None else ()
    )
    with _POP_CACHE_LOCK:
        fn = _POP_CACHE.get(key)
        if fn is None:
            vmapped, sharded = _LANE_OPS[op]
            width = _mesh_width(mesh, axis) if mesh is not None else 1
            if mesh is None:
                built = vmapped(tc)
            elif width > 1 and op not in _READONLY_LANE_OPS:
                rules = _tp_rules_or_raise(tc, width)
                out_sh = two_level_state_specs(
                    _population_state_shapes(tc, int(population)),
                    _state_logical_specs(tc), mesh, axis=axis, rules=rules)
                fn = jax.jit(vmapped(tc), donate_argnums=0,
                             out_shardings=out_sh)
                _POP_CACHE[key] = fn
                return fn
            elif width > 1:
                # snapshot/regrid: GSPMD, output layout decided by the caller
                # (snapshot is host-harvested; regrid re-lays out via
                # place_two_level on the NEW mesh)
                fn = jax.jit(vmapped(tc))
                _POP_CACHE[key] = fn
                return fn
            else:
                built = sharded(tc, mesh, axis=axis)
            if op in _READONLY_LANE_OPS:
                fn = jax.jit(built)
            else:
                fn = jax.jit(built, donate_argnums=0)
            _POP_CACHE[key] = fn
    return fn


def get_compiled_reset_lanes(tc: TrainConfig, population: int):
    """Memoized ``jax.jit`` of the lane-refill reset with donated state."""
    return get_compiled_lane_op(tc, population, "init")


def get_compiled_sharded_reset_lanes(
    tc: TrainConfig,
    population: int,
    mesh: Optional[Mesh] = None,
    axis: str = "pop",
):
    """Memoized jitted ``shard_map`` lane reset over ``mesh`` (keyed like the
    sharded population step, so one refill flight compiles exactly two
    programs: step + reset)."""
    mesh = mesh if mesh is not None else population_mesh(axis=axis)
    return get_compiled_lane_op(tc, population, "init", mesh=mesh, axis=axis)


def clear_population_cache() -> None:
    with _POP_CACHE_LOCK:
        _POP_CACHE.clear()


def count_model_axis_collectives(
    tc: TrainConfig,
    population: int,
    mesh: Mesh,
    data,
    per_trial_batch: bool = False,
    axis: str = "pop",
) -> int:
    """All-reduce count in the lowered population step — the static witness
    that the model axis carries compute.

    The per-step twin has NO population-axis collectives (lanes are
    embarrassingly parallel; the rule twins' all_gathers live in other
    programs), so every all-reduce in its HLO is a model-axis psum from the
    TP seams.  Width 1 must lower to exactly zero.  Abstract (eval_shape)
    arguments only — nothing is allocated.
    """
    from ..launch.hlo_stats import parse_collectives
    from ..optim.hparams import hparams_from_config

    k = int(population)
    step = get_compiled_sharded_population_step(
        tc, k, mesh=mesh, per_trial_batch=per_trial_batch, axis=axis)
    pstate = _population_state_shapes(tc, k)
    bshape = (k, data.global_batch) if per_trial_batch else (data.global_batch,)
    batch = {
        "tokens": jax.ShapeDtypeStruct(bshape + (data.seq_len,), jnp.int32),
        "targets": jax.ShapeDtypeStruct(bshape + (data.seq_len,), jnp.int32),
        "mask": jax.ShapeDtypeStruct(bshape + (data.seq_len,), jnp.float32),
    }
    hp = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((k,), jnp.asarray(x).dtype),
        hparams_from_config(tc))
    txt = step.lower(pstate, batch, hp).compile().as_text()
    width = _mesh_width(mesh, axis)
    stats = parse_collectives(txt, default_group=max(width, 1))
    return int(stats.per_op.get("all-reduce", {}).get("count", 0))


def population_scores(pstate: PopState, diverged_score: float = -1e9):
    """HPO convention: score = -final_loss, with a sentinel for diverged trials.

    Trials that never applied a step (budget 0) also get the sentinel.
    """
    last = pstate["last_loss"]
    ok = ~pstate["diverged"] & jnp.isfinite(last)
    return jnp.where(ok, -last, jnp.float32(diverged_score))
