"""Seeded weights: the base parameters and the experts' packed planes.

Both are made on the device from ``--seed`` in one jitted call each, in
the dtype they are served in.  Every leaf is generated one unit (layer)
at a time from its own key, ``fold_in(root, kind, leaf, unit)``, so the
plain reference regenerates any single layer with the same functions
(:func:`base_unit`, :func:`ternary_unit`) without holding the whole
model.  Nothing here calls the program: it only lays the leaves out as
the program's parameter tree and ``PackedTernary`` planes expect (flat C
order, bit ``i % 32`` of word ``i // 32``); the planes are drawn as words
directly, so no dense ternary tensor is made or packed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

LANE = 32
DENSITY = 7 / 64             # share of nonzero expert entries
_BASE, _EXPERT = 1, 2
NORMS = ("pre_norm", "ffn_norm", "final_norm", "q_norm", "k_norm")
BIASES = ("bq", "bk", "bv")
EXPERT_SCALE_OF_STD = 0.25   # an expert's |delta| as a share of the leaf std
NORM_EXPERT_SCALE = 0.05
# a block leaf stacked over routed experts (the program's wg_e, wu_e, wo_e)
EXPERT_AXIS_SUFFIX = "_e"


def root_key(seed: int) -> jax.Array:
    """A key from any whole number that fits 64 bits."""
    s = int(seed) % (1 << 64)
    k = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(s >> 32))


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter leaf: its path, index, unit count (0 = not stacked
    over layers), per-unit shape and how its values are drawn."""
    path: str
    index: int
    units: int
    core: tuple
    dtype: object

    @property
    def name(self) -> str:
        return self.path.split("/")[-1]

    @property
    def shape(self) -> tuple:
        return ((self.units,) if self.units else ()) + self.core

    @property
    def std(self) -> float:
        """Standard deviation of the base values (0: a constant leaf)."""
        if self.name in NORMS:
            return 0.0
        if self.name == "embed" or self.name in BIASES:
            return 0.02
        name, core = self.name, self.core
        if self.units and name.endswith(EXPERT_AXIS_SUFFIX):
            # [E, ...]: one routed expert's matrix per entry of the axis
            name, core = name[:-len(EXPERT_AXIS_SUFFIX)], core[1:]
        fan_in = int(np.prod(core[:-1])) if name == "wo" else core[0]
        return float(1.0 / np.sqrt(fan_in))

    @property
    def expert_scale(self) -> float:
        """|delta| of every nonzero expert entry on this leaf."""
        if self.name in NORMS:
            return NORM_EXPERT_SCALE
        return EXPERT_SCALE_OF_STD * self.std


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaves(shapes) -> list[Leaf]:
    """The leaves of a parameter-shape tree (``jax.eval_shape`` of the
    program's init), in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        p = _path_str(path)
        units = s.shape[0] if p.startswith("blocks/") else 0
        core = tuple(s.shape[1:]) if units else tuple(s.shape)
        if int(np.prod(core)) % LANE:
            raise ValueError(f"{p}: {core} is not a whole number of "
                             f"{LANE}-bit words per unit")
        out.append(Leaf(p, i, units, core, s.dtype))
    return out


def _unit_key(root, kind: int, *ids) -> jax.Array:
    k = jax.random.fold_in(root, kind)
    for i in ids:
        k = jax.random.fold_in(k, i)
    return k


def base_unit(root, leaf: Leaf, unit) -> jax.Array:
    """Unit ``unit`` of a base leaf, in the leaf's dtype."""
    if leaf.std == 0.0:
        return jnp.ones(leaf.core, leaf.dtype)
    k = _unit_key(root, _BASE, leaf.index, unit)
    return (leaf.std * jax.random.normal(k, leaf.core, jnp.float32)
            ).astype(leaf.dtype)


def planes_unit(root, leaf: Leaf, expert, unit):
    """Unit ``unit`` of expert ``expert``'s planes on a leaf: uint32
    ``(pos, neg)`` words over the unit's entries in C order.

    An entry is nonzero where three random bits are all set and one of
    three more is (7/64, about 0.11 of the entries); a seventh bit gives
    its sign."""
    k = _unit_key(root, _EXPERT, expert, leaf.index, unit)
    w = jax.random.bits(k, (7, int(np.prod(leaf.core)) // LANE), jnp.uint32)
    nz = w[0] & w[1] & w[2] & (w[3] | w[4] | w[5])
    return nz & w[6], nz & ~w[6]


def ternary_unit(root, leaf: Leaf, expert, unit) -> jax.Array:
    """The same unit's ternary signs, int8 in {-1, 0, +1}, in the leaf's
    per-unit shape."""
    pos, neg = planes_unit(root, leaf, expert, unit)
    shifts = jnp.arange(LANE, dtype=jnp.uint32)
    p = ((pos[:, None] >> shifts) & 1).astype(jnp.int8)
    n = ((neg[:, None] >> shifts) & 1).astype(jnp.int8)
    return (p - n).reshape(leaf.core)


def _per_unit(leaf: Leaf, fn):
    """``fn(unit)`` for every unit of a leaf, stacked (one call when the
    leaf has no unit axis)."""
    if not leaf.units:
        return fn(0)
    return jax.lax.map(fn, jnp.arange(leaf.units))


def make_base(shapes, seed: int):
    """The base parameter tree, made on the device in one jitted call."""
    lv = leaves(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    @jax.jit
    def build(root):
        return jax.tree_util.tree_unflatten(treedef, [
            _per_unit(lf, lambda u, lf=lf: base_unit(root, lf, u))
            for lf in lv])

    return build(root_key(seed))


def make_planes(shapes, seed: int, n_experts: int):
    """Each expert's planes as ``[(pos, neg, scale)]`` per leaf, made on
    the device in one jitted call."""
    lv = leaves(shapes)

    @jax.jit
    def build(root):
        out = []
        for e in range(n_experts):
            per = []
            for lf in lv:
                pos, neg = _per_unit(
                    lf, lambda u, lf=lf, e=e: planes_unit(root, lf, e, u))
                per.append((pos.reshape(-1), neg.reshape(-1),
                            jnp.float32(lf.expert_scale)))
            out.append(per)
        return out

    return lv, build(root_key(seed))


def make_experts(shapes, seed: int, n_experts: int) -> list:
    """The experts as the program's ``Expert`` artifacts over planes made
    by :func:`make_planes` (no compression pipeline runs)."""
    from repro.core.packing import PackedTernary
    from repro.expert import Expert

    treedef = jax.tree_util.tree_structure(shapes)
    lv, planes = make_planes(shapes, seed, n_experts)
    out = []
    for e, per in enumerate(planes):
        packed = [PackedTernary(pos=p, neg=n, scale=s, shape=lf.shape,
                                orig_dtype=lf.dtype)
                  for lf, (p, n, s) in zip(lv, per)]
        out.append(Expert.from_packed(
            f"expert{e}", "full",
            jax.tree_util.tree_unflatten(treedef, packed),
            density=DENSITY, alpha=1.0))
    return out
