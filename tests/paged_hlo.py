"""Reading a compiled paged decode step's HLO text: which pool leaves it
updates in place, and which of its ops copy, slice or transpose a whole
pool leaf or one layer of it."""
import re

import jax

#: ``name = dtype[dims]{layout} opcode(`` in HLO text
_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
#: the entry parameters of an argument named ``caches``: by their name, or
#: in a program partitioned over a mesh by their ``op_name``
_PARAM = re.compile(r"%(?:caches\S* = \S+ parameter\((\d+)\)"
                    r"|\S+ = \S+ parameter\((\d+)\).*op_name=\"caches\[)")
_ALIAS = re.compile(r"\{[\d,\s]*\}: \((\d+), \{\}, may-alias\)")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "transpose")


def unaliased_pool_leaves(text: str, caches) -> int:
    """Pool leaves (of the ``caches`` argument) not aliased to an output."""
    params = {int(a or b) for a, b in _PARAM.findall(text)}
    assert len(params) == len(jax.tree.leaves(caches))
    return len(params - {int(n) for n in _ALIAS.findall(text)})


def pool_sized_moves(text: str, caches) -> list:
    """Copies, slices and transposes shaped like a pool leaf or a layer
    (of one device's shard, where a leaf has a sharding)."""
    shapes = set()
    for leaf in jax.tree.leaves(caches):
        sharding = getattr(leaf, "sharding", None)
        shape = tuple(sharding.shard_shape(leaf.shape) if sharding
                      else leaf.shape)
        shapes |= {shape, shape[1:]}
    bad = []
    for line in text.splitlines():
        m = _OP.match(line)
        if m and m.group(2) in _MOVES and tuple(
                int(d) for d in m.group(1).split(",") if d) in shapes:
            bad.append(line.strip()[:120])
    return bad
