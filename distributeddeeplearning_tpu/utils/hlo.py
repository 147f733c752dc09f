"""Compiled-HLO inspection helpers.

The defense against silent-replication regressions (round 2: two strategy
rows passed every loss-parity test while emitting zero collectives): strategy
tests compile their real train step and assert the program *does* what the
strategy means — Ulysses emits all-to-alls, Megatron-SP the seq regather,
ring its collective-permutes, EP its token exchange (see
``tests/test_hlo_collectives.py``).
"""

from __future__ import annotations

import re

# Collective mnemonics as they appear in compiled HLO text. ``reduce-scatter``
# may legitimately be absent on backends that lower it as
# all-reduce + dynamic-slice (the CPU emitter does); tests therefore assert
# on the gather side and on deltas vs a control compile.
COLLECTIVE_KINDS: tuple[str, ...] = (
    "all-to-all",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-reduce",
)


def collective_counts(hlo_text: str) -> dict[str, int]:
    """Count collective ops in compiled HLO text."""
    return {k: len(re.findall(k, hlo_text)) for k in COLLECTIVE_KINDS}


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# One op definition line: `%name = <type> <kind>(...)`, where <type> is a
# shaped type or a tuple of them — long tuples carry `/*index=N*/` comments
# inside the type, so the type match is a lazy wildcard anchored between
# "= " and " <kind>(". The kind must be followed by "(" so the
# `-start`/`-done` async halves and `-start` fusions don't double-count
# (async pairs share one `-start(` definition; the `-done` line's operand
# is the start's result, and its own type repeats the payload — match only
# the `-start` / sync form).
_OP_LINE = re.compile(
    r"= (?P<type>\(?.*?\)?) "
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?P<start>-start)?\("
)
_SHAPE = re.compile(r"(?P<dt>[a-z]+[0-9]*)\[(?P<dims>[0-9,]*)\]")
# `replica_groups={{0,1},{2,3}}` (explicit) or `replica_groups=[4,2]<=[8]`
# (iota: 4 groups of 2).
_GROUPS_EXPLICIT = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _type_bytes(type_str: str, start_op: bool = False) -> int:
    """Byte size of a shaped type or tuple of them. ``start_op`` counts
    just the LARGEST element: an async ``-start`` op's tuple type is
    ``(operand, result, scratch/flag entries...)`` — on TPU,
    collective-permute-start appends ``u32[]`` flags, so "last element"
    would read 4 bytes — and summing would double-count the payload. The
    largest element is the payload under this module's conventions for
    every kind (all-gather: the gathered result; reduce-scatter: the
    full input; all-reduce/permute: operand == result)."""
    sizes = []
    for m in _SHAPE.finditer(type_str):
        size = _DTYPE_BYTES.get(m.group("dt"))
        if size is None:
            continue  # token/opaque types carry no payload
        n = 1
        for d in m.group("dims").split(","):
            if d:
                n *= int(d)
        sizes.append(n * size)
    if start_op:
        return max(sizes) if sizes else 0
    return sum(sizes)


def collective_bytes(hlo_text: str, n_devices: int) -> dict[str, list]:
    """Per-kind ``[(payload_bytes, group_size), ...]`` of every collective
    in compiled HLO text. ``payload_bytes`` is the op's OUTPUT type size
    (for all-gather that is the gathered size; callers apply the per-kind
    ring-cost formula). ``group_size`` comes from ``replica_groups``
    (explicit or iota form); ops without a parsable group default to
    ``n_devices``. The tests of the gradient-sync byte cuts
    (``tests/helpers.sync_wire_bytes``) count from it; a byte count is not
    a time (no four-chip cell: PERF.md §7)."""
    out: dict[str, list] = {k: [] for k in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = _OP_LINE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        payload = _type_bytes(
            m.group("type"), start_op=bool(m.group("start"))
        )
        if not payload:
            continue
        g = _GROUPS_EXPLICIT.search(line)
        if g:
            group = len(g.group(1).split(","))
        else:
            g = _GROUPS_IOTA.search(line)
            group = int(g.group(2)) if g else n_devices
        if kind == "reduce-scatter" and not m.group("start"):
            # The sync form's definition type is the SCATTERED output
            # (full_input / group) while the async ``-start`` tuple's
            # largest element is the full input — without this the same
            # program's RS bytes shrank ~group_size-fold depending on
            # which form the backend emitted. Normalize both to the
            # full-input convention the docstring (and the ring-cost
            # formulas downstream) assume.
            payload *= group
        out[kind].append((payload, group))
    return out
