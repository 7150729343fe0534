"""Sharding plans: how each (family × step-kind) maps onto the mesh.

Baseline layouts (see EXPERIMENTS.md §Perf for the hillclimbed variants):

  * attention-family **train/prefill**: batch→batch_axes, seq→``seq_axis``
    (ring attention over `model`), weights fully sharded over
    (data×model) on their largest dim (ZeRO-3 / FSDP — all-gathered per
    layer, overlappable on TPU), optimizer state sharded identically.
  * ssm/hybrid **train**: width→``width_axis`` TP (heads / LRU channels are
    embarrassingly parallel) + FSDP over `data` on the other weight dim.
  * all **decode**: batch→batch_axes, weights row/col-sharded over
    ``width_axis`` (resident TP — no per-step weight gathers), KV cache
    seq-sharded over ``cache_seq_axes`` with LSE-combined partial attention
    (supports every GQA kv-head count, incl. kv=1); for global_batch=1
    (long_500k) the cache seq-shards over BOTH (data, model).

All specs are produced here so a hillclimb iteration is a plan edit, not a
model edit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ()       # activation batch dim
    seq_axis: str = ""                     # activation seq dim (train/prefill)
    width_axis: str = ""                   # TP width axis (ssm/hybrid, serve)
    fsdp_axes: Tuple[str, ...] = ()        # weight-shard axes (train)
    cache_seq_axes: Tuple[str, ...] = ()   # KV-cache seq dim (serve)
    kv_quant: bool = False                 # int8 KV cache (beyond-paper)
    expert_quant: bool = False             # weight-only int8 experts (serve)
    attn_batch_shard: bool = False         # reshard attn batch over seq axis
                                           # (kills ring traffic when
                                           #  B % (data*model) == 0)
    remat: bool = False
    unroll: bool = False                   # analysis mode: unroll inner loops
    mode: str = "train"                    # train | prefill | decode

    # ------------------------------------------------------------------ #
    def axis_size(self, *names: str) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for name in names:
            if name:
                n *= self.mesh.shape[name]
        return n

    @property
    def n_seq(self) -> int:
        return self.axis_size(self.seq_axis)

    @property
    def n_width(self) -> int:
        return self.axis_size(self.width_axis)

    @property
    def n_cache(self) -> int:
        return self.axis_size(*self.cache_seq_axes)

    def _fits(self, dim: int, axes) -> bool:
        axes = axes if isinstance(axes, tuple) else (axes,)
        return dim % max(self.axis_size(*axes), 1) == 0

    # ------------------------------------------------------------------ #
    def dp(self):
        return self.batch_axes if self.batch_axes else None

    def act_spec(self, ndim: int = 3):
        """(B, S, d) activation spec."""
        seq = self.seq_axis or None
        return P(self.dp(), seq, *([None] * (ndim - 2)))

    def constrain(self, x, spec=None):
        if self.mesh is None:
            return x
        spec = spec if spec is not None else self.act_spec(x.ndim)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------ #
    # parameter specs
    # ------------------------------------------------------------------ #
    _COL = ("w_gate", "w_up", "w_z", "w_x", "w_dt", "wq", "wk", "wv",
            "unembed", "w_gate_in")
    _ROW = ("w_down", "w_out", "wo")
    _SMALL = ("router", "scale", "bias", "a_log", "dt_bias", "d_skip",
              "a_param", "w_bc", "conv_bc", "bq", "bk", "bv")

    def param_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]):
        name = path[-1]
        is_expert = "moe" in path
        is_lora = "lora" in path or name.startswith(("a_", "b_")) and \
            name in ("a_q", "b_q", "a_v", "b_v")
        nd = len(shape)
        none = [None] * nd

        if is_lora:
            return P(*none)
        if name in self._SMALL and not is_expert:
            return P(*none)

        w = self.width_axis or None

        if is_expert and name in ("w_gate", "w_up", "w_down", "router",
                                  "w_gate_scale", "w_up_scale",
                                  "w_down_scale"):
            if name == "router":
                return P(*none)
            if name.endswith("_scale"):     # (R?, E, 1, ff): E over EP axis
                spec = list(none)
                ep = (self.width_axis or self.seq_axis) or None
                if ep and self._fits(shape[nd - 3], ep):
                    spec[nd - 3] = ep
                return P(*spec)
            # (R?, E, d, ff) / (R?, E, ff, d): experts over EP axis
            spec = list(none)
            ep = (self.width_axis or self.seq_axis) or None
            e_dim = nd - 3
            if ep and self._fits(shape[e_dim], ep):
                spec[e_dim] = ep
            if self.fsdp_axes:
                ff_dim = nd - 1 if name != "w_down" else nd - 2
                if spec[ff_dim] is None and self._fits(shape[ff_dim], "data"):
                    spec[ff_dim] = "data"
            return P(*spec)

        spec = list(none)
        if name in ("w_a", "w_i"):  # (R?, nb, wb, wb) block-diagonal gates
            if w and self._fits(shape[nd - 3], w):
                spec[nd - 3] = w
            return P(*spec)
        if name in ("conv_x", "conv_w"):
            if w and self._fits(shape[nd - 1], w):
                spec[nd - 1] = w
            return P(*spec)
        if name == "embed":
            if self.mode == "train" and self.fsdp_axes and \
                    self._fits(shape[0], self.fsdp_axes):
                return P(self.fsdp_axes, None)
            return P(*none)

        if w and name in self._COL and self._fits(shape[nd - 1], w):
            spec[nd - 1] = w
        elif w and name in self._ROW and self._fits(shape[nd - 2], w):
            spec[nd - 2] = w

        if self.mode == "train" and self.fsdp_axes:
            # FSDP: shard the largest still-unsharded dim
            cands = sorted(range(max(nd - 2, 0), nd),
                           key=lambda i: -shape[i])
            for i in cands:
                if spec[i] is None and self._fits(shape[i], self.fsdp_axes):
                    spec[i] = self.fsdp_axes
                    break
        return P(*spec)

    def param_specs(self, params):
        def walk(path, leaf):
            names = tuple(
                p.key if hasattr(p, "key") else str(p) for p in path)
            return self.param_spec(names, leaf.shape)
        return jax.tree_util.tree_map_with_path(walk, params)

    def shardings(self, tree_of_specs):
        if self.mesh is None:
            return None
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            tree_of_specs,
                            is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------------ #
    # cache specs
    # ------------------------------------------------------------------ #
    def cache_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]):
        name = path[-1]
        nd = len(shape)
        if name == "pos":
            return P()
        dp = self.dp()
        w = self.width_axis or None
        cache_seq = self.cache_seq_axes if self.cache_seq_axes else None
        if name in ("k", "v"):             # (R, B, KV, S, D) global layers
            return P(None, dp, None, cache_seq, None)
        if name in ("k_scale", "v_scale"):  # (R, B, KV, S) int8-KV scales
            return P(None, dp, None, cache_seq)
        if name in ("k_loc", "v_loc"):     # (R, B, W, KV, D) rolling
            return P(None, dp, None, None, None)
        if name in ("conv_x", "conv"):     # (R, B, cw-1, C@width)
            sp = [None] * nd
            sp[1] = dp
            if w and shape[-1] % max(self.axis_size(w), 1) == 0:
                sp[-1] = w
            return P(*sp)
        if name == "conv_bc":
            return P(None, dp, None, None)
        if name == "ssm":                  # (R, B, H@width, p, n)
            sp = [None, dp, None, None, None]
            if w and shape[2] % max(self.axis_size(w), 1) == 0:
                sp[2] = w
            return P(*sp)
        if name == "lru":                  # (R, B, W@width)
            sp = [None, dp, None]
            if w and shape[2] % max(self.axis_size(w), 1) == 0:
                sp[2] = w
            return P(*sp)
        return P(*([None] * nd))

    def cache_specs(self, cache):
        def walk(path, leaf):
            names = tuple(
                p.key if hasattr(p, "key") else str(p) for p in path)
            return self.cache_spec(names, leaf.shape)
        return jax.tree_util.tree_map_with_path(walk, cache)


# --------------------------------------------------------------------------- #
# canonical plans
# --------------------------------------------------------------------------- #

def make_plan(cfg, mesh: Optional[Mesh], kind: str, *,
              unroll: bool = False, remat: bool = False,
              global_batch: int = 1, kv_quant: bool = False) -> ShardingPlan:
    """Baseline plan for (family, step kind)."""
    if mesh is None:
        return ShardingPlan(mode="train" if kind == "train" else kind,
                            unroll=unroll, remat=remat, kv_quant=kv_quant)
    axes = dict(mesh.shape)
    has_pod = "pod" in axes
    # ssm/hybrid keep full seq (recurrence) and use width-TP everywhere
    width_tp_family = cfg.family in ("ssm", "hybrid")

    batch_axes: Tuple[str, ...] = ("pod", "data") if has_pod else ("data",)
    n_batch = 1
    for a in batch_axes:
        n_batch *= axes[a]
    if global_batch % max(n_batch, 1) != 0 or global_batch < n_batch:
        batch_axes = ("data",) if global_batch % axes.get("data", 1) == 0 \
            and global_batch >= axes.get("data", 1) else ()

    if kind == "train":
        return ShardingPlan(
            mesh=mesh, batch_axes=batch_axes,
            seq_axis="" if width_tp_family else "model",
            width_axis="model" if width_tp_family else "",
            fsdp_axes=("data",) if width_tp_family else ("data", "model"),
            remat=remat, unroll=unroll, mode="train")
    if kind == "prefill":
        return ShardingPlan(
            mesh=mesh, batch_axes=batch_axes,
            seq_axis="" if width_tp_family else "model",
            width_axis="model",
            cache_seq_axes=("model",), kv_quant=kv_quant,
            unroll=unroll, mode="prefill")
    # decode
    cache_axes: Tuple[str, ...] = ("model",)
    if not batch_axes:  # global_batch=1 (long_500k): seq over data too
        cache_axes = ("data", "model")
    return ShardingPlan(
        mesh=mesh, batch_axes=batch_axes,
        seq_axis="", width_axis="model",
        cache_seq_axes=cache_axes, kv_quant=kv_quant,
        unroll=unroll, mode="decode")
