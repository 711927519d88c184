"""MoE layer — expert-parallel mixture of experts over the 'expert' mesh axis.

Capability parity with the reference's ``deepspeed/moe/layer.py`` (MoE wrapper),
``experts.py`` (local expert stack) and the MOELayer dispatch pipeline
(sharded_moe.py:439: gate -> einsum dispatch -> all_to_all -> expert ->
all_to_all -> einsum combine).

TPU-native execution, two paths:
  * The flax module uses sharding *constraints*: expert weights are stacked
    [E, ...] and constrained to P("expert", ...); the dispatched queue
    [E, C, H] is constrained to P("expert"). XLA's SPMD partitioner inserts
    the token exchange (the reference's `_AllToAll` autograd fn over the
    expert group, sharded_moe.py:89) automatically from the sharding
    mismatch between token-sharded gating and expert-sharded compute.
  * `expert_parallel_apply` is the explicit collective path — a partial-auto
    shard_map whose `lax.all_to_all` pair is exactly GShard's exchange — used
    where hand-placement beats the partitioner and as the comm-correctness
    oracle in tests.

The batch axis is sharded over ("data","expert") — EP is carved out of DP
exactly as the reference carves expert groups from DP ranks
(utils/groups.py:109-262).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharded_moe import compute_capacity, top1_gating, top2_gating

#: canonical intermediate PartitionSpecs of the dispatch pipeline —
#: module-level constants so every reshape lands on the SAME spelling
#: (graftlint TPU008 resolves P(...) literals through these names) and
#: the grouped-layout transitions stay expressible as collectives
#: instead of SPMD replicate-and-reshard fallbacks (ROADMAP item 2a)
TOKEN_AXES = ("data", "expert", "seq")
QUEUE_SPEC = P("expert", ("data", "seq"))
GROUP_SPEC = P(TOKEN_AXES)


def _constrain(x, *spec):
    """Sharding constraint that works under plain jax.jit (resolved against
    the session's global mesh) and inside shard_map contexts (bare spec) —
    see models/transformer._spec_constraint for the rationale."""
    from ..models.transformer import _spec_constraint
    return _spec_constraint(x, P(*spec))


def _warn_ungrouped_fallback(T: int, g: int) -> None:
    """Once-per-(T, g) signal that the dispatch dropped to the ungrouped
    (G=1) layout: token count not divisible by the data*expert*seq mesh
    product reverts to the rematerialization-prone path, and a silent
    fallback makes the resulting perf regression undiagnosable from logs."""
    import jax as _jax
    if _jax.process_index() != 0:
        return
    from ..utils.logging import warning_once
    warning_once(
        f"MoE grouped dispatch disabled: tokens-per-step {T} is not "
        f"divisible by the data*expert*seq mesh product {g}; falling back "
        "to the ungrouped GShard layout, which may trigger involuntary "
        "rematerialization reshards. Pad batch*seq to a multiple of the "
        "mesh product to restore the grouped layout.")


class _Gate(nn.Module):
    """Router projection with the kernel pinned replicated.

    Under ZeRO-3 the [H, E] kernel arrives sharded on its CONTRACTING dim;
    left alone, GSPMD partitions the dot along H and reshards the token
    activations to match — the "involuntary full rematerialization" on the
    moe reshape. Gathering the (tiny) kernel whole instead keeps tokens on
    their batch sharding. Param path stays gate/kernel (nn.Dense parity)."""
    experts: int
    # a dropless mixture's selection bias, ``bias [experts]`` (zeros; its
    # owner updates it outside the gradient): ``kernel_only`` then returns
    # ``(kernel, bias)``
    select_bias: bool = False

    @nn.compact
    def __call__(self, x, kernel_only: bool = False):
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (x.shape[-1], self.experts), jnp.float32)
        k = _constrain(k, None, None)
        if self.select_bias:
            return k, self.param("bias", nn.initializers.zeros_init(),
                                 (self.experts,), jnp.float32)
        return k if kernel_only else x @ k


class ExpertMLP(nn.Module):
    """Default expert: the transformer MLP (fc -> gelu -> proj)."""
    hidden_size: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.mlp_dim, use_bias=self.use_bias, dtype=self.dtype,
                     param_dtype=jnp.float32, name="fc")(x)
        h = nn.gelu(h)
        return nn.Dense(self.hidden_size, use_bias=self.use_bias,
                        dtype=self.dtype, param_dtype=jnp.float32,
                        name="proj")(h)


class GatedExpertMLP(nn.Module):
    """SwiGLU expert (Mixtral-family: HF MixtralBlockSparseTop2MLP w1/w3/w2):
    proj(act(gate(x)) * fc(x)) — the 3-matmul gated MLP as an expert body.
    Param names mirror the dense block's mlp_gate/mlp_fc/mlp_proj roles."""
    hidden_size: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    use_bias: bool = False
    activation: str = "silu"

    @nn.compact
    def __call__(self, x):
        from ..models.transformer import _ACTIVATIONS
        act = _ACTIVATIONS[self.activation]
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=jnp.float32, name=name)
        g = act(dense(self.mlp_dim, "gate")(x))
        h = g * dense(self.mlp_dim, "fc")(x)
        return dense(self.hidden_size, "proj")(h)


class MoE(nn.Module):
    """Mixture-of-experts block: gate + dispatch + expert-parallel compute.

    Returns (y, aux_loss); callers fold aux_loss into the task loss
    (reference: MoE.forward returns (output, l_aux, exp_counts), layer.py:15).
    """
    hidden_size: int
    num_experts: int
    expert: Optional[Callable[[], nn.Module]] = None
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    # the default expert body, when ``expert`` is None: width, SwiGLU
    # (``GatedExpertMLP``) or fc -> gelu -> proj (``ExpertMLP``), biases
    mlp_dim: Optional[int] = None
    gated: bool = False
    activation: str = "silu"
    use_bias: bool = True
    # facts of the architecture, not speed switches: ``dropless`` routes
    # every token to all its k experts with no capacity (always so for
    # k > 2, which the GShard tensors cannot carry); ``norm_topk``
    # renormalises the k weights (HF ``norm_topk_prob``)
    dropless: bool = False
    norm_topk: bool = True
    # dropless only: return the ``[2, E]`` balance statistics in place of
    # this layer's scalar loss, for a model that averages them over layers
    aux_stats: bool = False
    # dropless only (``moe/dropless.dropless_moe`` says what each means):
    # the router's scores, its selection bias, the picks' scale, the share
    # of the ``num_experts`` this module holds, a shared expert's width
    scores: str = "softmax"
    select_bias: bool = False
    routed_scale: float = 1.0
    held: Optional[Tuple[int, int]] = None
    # group-limited selection (n groups, keep), moe/dropless.kept_groups
    groups: Optional[Tuple[int, int]] = None
    shared_dim: int = 0

    @property
    def is_dropless(self) -> bool:
        return self.dropless or self.k > 2

    @property
    def expert_width(self) -> int:
        return self.mlp_dim or self.hidden_size * self.mlp_ratio

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.is_dropless:
            return self._dropless(x)
        B, S, H = x.shape
        E = self.num_experts
        tokens = x.reshape(B * S, H)
        # the merged token dim inherits the batch x seq product sharding —
        # spell it out so SPMD doesn't fall back to replicate-and-reshard
        # (the "involuntary full rematerialization" warning on this reshape)
        tokens = _constrain(tokens, ("data", "expert", "seq"), None)
        T = B * S

        # GShard data layout (reference: sharded_moe.py:89,439 — each rank
        # gates its OWN token slice into a local-capacity queue, then the
        # expert axis exchanges queues with an all-to-all): tokens regroup as
        # [G, T/G, H] with G matching the token dim's mesh sharding, gating
        # runs per group, and the [E, G*Cg, H] queue carries the expert axis
        # on E and the data axes on the queue dim. Without the grouping the
        # partitioner has no valid data-sharded queue layout and falls back
        # to involuntary full rematerialization of the token tensor.
        from ..parallel.mesh import get_global_mesh
        mm = get_global_mesh()
        G = 1
        if mm is not None:
            g = (mm.shape["data"] * mm.shape["expert"] * mm.shape["seq"])
            if T % g == 0:
                G = g
            elif g > 1:
                _warn_ungrouped_fallback(T, g)
        Tg = T // G

        tokens_g = _constrain(tokens.reshape(G, Tg, H),
                              ("data", "expert", "seq"), None, None)
        gate_logits = _Gate(E, name="gate")(
            tokens_g.astype(jnp.float32))                    # [G, Tg, E]
        # top-2 always wants an rng for the Gumbel-max second pick (reference
        # top2gating adds gumbel noise unconditionally in training); fall back
        # to noise-free gating when the caller supplied no "gating" rng stream
        rng = (self.make_rng("gating")
               if train and (self.noisy_gate_policy == "RSample" or self.k == 2)
               and self.has_rng("gating")
               else None)
        cf = self.capacity_factor if train else self.eval_capacity_factor
        Cg = compute_capacity(Tg, E, cf, self.k, self.min_capacity)
        gating = top1_gating if self.k == 1 else top2_gating
        if self.k not in (1, 2):
            raise ValueError(f"k must be 1 or 2, got {self.k}")
        if rng is None:
            gate_one = lambda lg: gating(lg, cf, self.min_capacity,
                                         rng=None, capacity=Cg)
            aux, combine, dispatch, _ = jax.vmap(gate_one)(gate_logits)
        else:
            gate_one = lambda lg, r: gating(lg, cf, self.min_capacity,
                                            rng=r, capacity=Cg)
            aux, combine, dispatch, _ = jax.vmap(gate_one)(
                gate_logits, jax.random.split(rng, G))
        aux = jnp.mean(aux)
        # combine/dispatch: [G, Tg, E, Cg] — group dim stays token-sharded
        dispatch = _constrain(dispatch, ("data", "expert", "seq"),
                              None, None, None)

        # per-group dispatch, then the queue exchange: [G,E,Cg,H] (group-
        # sharded) -> [E, G*Cg, H] (expert-sharded E, data-sharded queue) is
        # the all-to-all of the reference's _AllToAll (sharded_moe.py:89)
        dispatched = jnp.einsum("gtec,gth->gech", dispatch.astype(self.dtype),
                                tokens_g.astype(self.dtype))
        from ..models.transformer import _spec_constraint
        dispatched = _spec_constraint(dispatched, GROUP_SPEC)

        # comm-plan seam: with an active plan routing the expert a2a to a
        # quantized wire format, the exchange pair runs EXPLICITLY (int8
        # payload + blockwise scales through comm.planned); otherwise the
        # canonical constraints below let the SPMD partitioner emit the
        # exact all-to-all from the sharding transition
        xchg_pair = None
        if mm is not None and G > 1 and G == g:
            from ..comm.planned import (moe_exchange_spec,
                                        planned_queue_exchange)
            xchg = moe_exchange_spec(
                mm, dispatched.size * dispatched.dtype.itemsize)
            if xchg is not None:
                algo, bits, blk = xchg
                xchg_pair = planned_queue_exchange(
                    mm.mesh, algo=algo, bits=bits, block=blk)
        if xchg_pair is not None:
            queues = xchg_pair[0](dispatched)            # [E, G*Cg, H]
        else:
            queues = dispatched.transpose(1, 0, 2, 3).reshape(E, G * Cg, H)
            queues = _spec_constraint(queues, QUEUE_SPEC)

        expert_factory = self.expert or self._default_expert
        vexpert = nn.vmap(
            lambda mdl, inp: mdl(inp),
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=0, out_axes=0,
            metadata_params={nn.PARTITION_NAME: "expert"},
        )
        expert_out = vexpert(expert_factory(), queues)       # [E, G*Cg, H]
        expert_out = _spec_constraint(expert_out, QUEUE_SPEC)

        # return exchange + per-group combine: the explicit pair inverts
        # the dispatch exchange exactly (row order is self-consistent)
        if xchg_pair is not None:
            out_g = xchg_pair[1](expert_out)             # [G, E, Cg, H]
        else:
            out_g = _spec_constraint(
                expert_out.reshape(E, G, Cg, H).transpose(1, 0, 2, 3),
                GROUP_SPEC)
        y = jnp.einsum("gtec,gech->gth", combine.astype(self.dtype),
                       out_g.astype(self.dtype))
        y = _constrain(y, ("data", "expert", "seq"), None, None)
        return y.reshape(B, S, H), aux.astype(jnp.float32)


    def _default_expert(self) -> nn.Module:
        width = self.expert_width
        if self.gated:
            return GatedExpertMLP(self.hidden_size, width, dtype=self.dtype,
                                  use_bias=self.use_bias,
                                  activation=self.activation, name="experts")
        return ExpertMLP(self.hidden_size, width, dtype=self.dtype,
                         use_bias=self.use_bias, name="experts")

    def _dropless(self, x):
        """Sorted-token dispatch (``moe/dropless.py``) over the same
        parameter tree: ``gate/kernel`` and ``experts/{gate,fc,proj}/kernel``
        stacked ``[E, in, out]``."""
        from ..models.transformer import _ACTIVATIONS
        from ..parallel.mesh import get_global_mesh
        from .dropless import balance_loss, balance_stats, dropless_moe
        if self.expert is not None:
            raise ValueError(
                "dropless MoE (k > 2 or dropless=True) runs the expert body "
                "as grouped matmuls over stacked kernels and takes no custom "
                "`expert` module: describe it with mlp_dim / gated / "
                "activation / use_bias")
        mm = get_global_mesh()
        if mm is not None and mm.shape["expert"] > 1:
            raise NotImplementedError(
                f"dropless MoE (k={self.k}) on an expert axis of "
                f"{mm.shape['expert']}: expert-parallel sorted dispatch is "
                "not implemented yet (ROADMAP R1's training half); run with "
                "ep=1, or k <= 2 for the GShard capacity path")
        B, S, H = x.shape
        width = self.expert_width
        tokens = _constrain(x.reshape(B * S, H), TOKEN_AXES, None)
        router_kernel = _Gate(self.num_experts, self.select_bias,
                              name="gate")(tokens, kernel_only=True)
        select_bias = None
        if self.select_bias:
            router_kernel, select_bias = router_kernel
        names = (("gate", H, width),) if self.gated else ()
        names += (("fc", H, width), ("proj", width, H))
        experts = _ExpertStack(
            self.held[1] if self.held else self.num_experts, names,
            self.use_bias, name="experts")()
        y, r = dropless_moe(
            tokens.astype(self.dtype), router_kernel, experts,
            k=self.k, renorm=self.norm_topk,
            act=_ACTIVATIONS[self.activation] if self.gated else nn.gelu,
            scores=self.scores, select_bias=select_bias,
            scale=self.routed_scale, held=self.held, groups=self.groups)
        if self.shared_dim:
            # the shared expert: the experts' body on every token, unweighted
            with jax.named_scope("shared"):
                body = (GatedExpertMLP(H, self.shared_dim, dtype=self.dtype,
                                       use_bias=self.use_bias,
                                       activation=self.activation,
                                       name="shared") if self.gated else
                        ExpertMLP(H, self.shared_dim, dtype=self.dtype,
                                  use_bias=self.use_bias, name="shared"))
                y = y + body(tokens.astype(self.dtype))
        y = _constrain(y, TOKEN_AXES, None).reshape(B, S, H)
        stats = balance_stats(r)
        return y, (stats if self.aux_stats else balance_loss(stats))


class _ExpertStack(nn.Module):
    """The stacked expert kernels under the names the vmapped expert
    modules give them: ``<name>/kernel [E, in, out]`` (+ ``bias [E, out]``),
    each expert drawn like one ``nn.Dense``."""
    experts: int
    names: Tuple[Tuple[str, int, int], ...]
    use_bias: bool

    @nn.compact
    def __call__(self):
        return {name: _StackedDense(self.experts, fan_in, out, self.use_bias,
                                    name=name)()
                for name, fan_in, out in self.names}


class _StackedDense(nn.Module):
    experts: int
    fan_in: int
    features: int
    use_bias: bool

    @nn.compact
    def __call__(self):
        p = {"kernel": self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)),
            (self.experts, self.fan_in, self.features), jnp.float32)}
        if self.use_bias:
            p["bias"] = self.param("bias", nn.initializers.zeros_init(),
                                   (self.experts, self.features), jnp.float32)
        return p


def expert_parallel_apply(apply_fn: Callable,
                          expert_params: Any,
                          dispatched: jnp.ndarray,
                          *,
                          mesh,
                          ep: int,
                          expert_axis: str = "expert") -> jnp.ndarray:
    """Explicit GShard exchange: all_to_all -> local experts -> all_to_all.

    apply_fn(params_of_one_expert, x [n, H]) -> [n, H]
    expert_params: stacked [E, ...] leaves, sharded P(expert_axis, ...)
    dispatched: [E, Cq, H] expert queues with the QUEUE dim sharded over the
    expert axis (each ep-rank built its own C = Cq/ep queue slots from its
    token slice — the GShard pre-exchange layout).
    Returns [E, Cq, H] with the same layout.
    """
    E, Cq, H = dispatched.shape
    if E % ep != 0 or Cq % ep != 0:
        raise ValueError(f"experts {E} / queue {Cq} not divisible by ep {ep}")

    def inner(params, disp):
        # disp local: [E, C, H] — this rank's queue slots for ALL experts.
        # exchange: give each rank the full queues of ITS local experts
        x = jax.lax.all_to_all(disp, expert_axis, split_axis=0, concat_axis=1,
                               tiled=True)            # [El, ep*C, H]
        y = jax.vmap(apply_fn)(params, x)             # [El, ep*C, H]
        return jax.lax.all_to_all(y, expert_axis, split_axis=1, concat_axis=0,
                                  tiled=True)         # [E, C, H] local again

    mapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(expert_axis), expert_params),
                  P(None, expert_axis)),
        out_specs=P(None, expert_axis),
        axis_names={expert_axis},
        check_vma=False,
    )
    # partial-auto shard_map requires a jit context (its eager trace path
    # rejects specs over auto axes); calling under jit is also the fast path
    # graftlint: disable=TPU002 (called under the model's outer jit: one construction per outer trace)
    return jax.jit(mapped)(expert_params, dispatched)
