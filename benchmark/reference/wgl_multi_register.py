"""Plain reference: linearizability of one multi-key register history.

The configuration search of Wing & Gong as knossos runs it (Lowe's
just-in-time form), as ``reference/wgl_register.py`` has it for one
register: walk the history in order; a configuration is (the keys' values,
the set of pending ops already linearized); when an op returns ``ok``,
close the set of configurations under linearizing any pending op, keep
those that linearized the returning op, and go on.  The history is
linearizable iff a configuration survives to the end; otherwise the
refuting op is the one whose return left none.

Independent of the program and of ``wgl_register``: imports nothing of
either, reads the benchmark's own plain records.  The model is knossos's
multi-register as the reference's multi-key workload defines it
(``yugabyte/src/yugabyte/multi_key_acid.clj``), an op's value a list of
``[key, value]`` pairs:

- a write sets all its keys atomically;
- a read asserts every key it observed; a key read as ``None`` asserts
  nothing (a nil read is always legal), and every key starts at ``None``;
- ``ok``   took effect exactly once between its invoke and its completion;
- ``fail`` did not take effect: the pair is dropped;
- ``info`` (crashed) may take effect at any time from its invoke on, or
  never: a crashed write stays pending for ever and is never required; a
  crashed read constrains nothing and is dropped;
- an ``ok`` read's value is its completion's (what it observed), a write's
  its invocation's.

One sound pruning keeps it within a minute at 10,000 ops, and changes no
verdict: of two configurations equal but for the crashed writes they
linearized, the one that linearized a subset can do everything the other
can (a crashed op is never required, and it can still be linearized
later), so only minimal sets are kept.

``beam`` is the control's handle, not the reference's: with ``beam=k`` the
closure keeps at most ``k`` configurations and calls a history refuted when
they die out, the truncated search that answers ``false`` where an honest
degraded search has to answer ``unknown``.  ``info_as_fail`` is the other
control: crashed ops read as failed.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

_WRITES = {"read": False, "r": False, "write": True, "w": True}


def _pair(records: Sequence[Any]) -> List[Tuple[int, Optional[int]]]:
    """(invoke position, completion position or None), in invoke order."""
    open_inv: Dict[Any, int] = {}
    done: Dict[int, int] = {}
    order: List[int] = []
    for i, o in enumerate(records):
        if o.type == "invoke":
            open_inv[o.process] = i
            order.append(i)
        else:
            j = open_inv.pop(o.process, None)
            if j is not None:
                done[j] = i
    return [(i, done.get(i)) for i in order]


def check(records: Sequence[Any], beam: Optional[int] = None,
          info_as_fail: bool = False) -> Dict[str, Any]:
    """``{"valid": True}`` or ``{"valid": False, "op_index": i}`` where ``i``
    is the position in ``records`` of the refuting op's invocation."""
    # -- the ops that take part, and the event stream --------------------
    paired = _pair(records)
    slot: Dict[Any, int] = {}            # key -> its place in the state
    for inv, _ in paired:
        for k, _v in records[inv].value or ():
            slot.setdefault(k, len(slot))
    # an op: (writes?, ((slot, value), ...), crashed?, invoke position)
    ops: List[Tuple[bool, Tuple[Tuple[int, Any], ...], bool, int]] = []
    enter_at: Dict[int, int] = {}
    return_at: Dict[int, int] = {}
    for inv, comp in paired:
        o = records[inv]
        ctype = records[comp].type if comp is not None else "info"
        if ctype == "fail" or (ctype == "info" and info_as_fail):
            continue
        writes = _WRITES[o.f]
        crashed = ctype == "info"
        if crashed and not writes:
            continue
        value = o.value if writes or crashed else records[comp].value
        touched = tuple((slot[k], v) for k, v in value or ()
                        if writes or v is not None)
        enter_at[inv] = len(ops)
        if not crashed:
            return_at[comp] = len(ops)
        ops.append((writes, touched, crashed, inv))

    # -- the search -------------------------------------------------------
    # pending: op id -> bit; a configuration is (values, ok-bits, crash-bits)
    bit_of: Dict[int, int] = {}
    free_bits: List[int] = []
    next_bit = 0
    pending: List[int] = []
    configs = {((None,) * len(slot), 0, 0)}
    for pos in range(len(records)):
        if pos in enter_at:
            i = enter_at[pos]
            if free_bits:
                bit_of[i] = free_bits.pop()
            else:
                bit_of[i] = 1 << next_bit
                next_bit += 1
            pending.append(i)
            continue
        if pos not in return_at:
            continue
        ret = return_at[pos]
        steps = [(bit_of[i],) + ops[i][:3] for i in pending]
        configs = _closure(configs, steps, beam)
        rbit = bit_of[ret]
        configs = {(s, okm & ~rbit, crm) for (s, okm, crm) in configs
                   if okm & rbit}
        if not configs:
            return {"valid": False, "op_index": ops[ret][3]}
        pending.remove(ret)
        free_bits.append(bit_of.pop(ret))
    return {"valid": True}


def _step(s, writes, touched):
    """The values after the op, or ``None`` where a read saw otherwise."""
    if writes:
        s = list(s)
        for i, v in touched:
            s[i] = v
        return tuple(s)
    for i, v in touched:
        if s[i] != v:
            return None
    return s


def _closure(configs, steps, beam):
    """Every configuration reachable by linearizing pending ops, minimal in
    the crashed ops used."""
    kept: Dict[Tuple[Any, int], List[int]] = {}

    def add(s, okm, crm) -> bool:
        got = kept.get((s, okm))
        if got is None:
            kept[(s, okm)] = [crm]
            return True
        for c in got:
            if c & ~crm == 0:
                return False
        got[:] = [c for c in got if crm & ~c != 0]
        got.append(crm)
        return True

    n = 0
    frontier = []
    for cfg in sorted(configs, key=repr) if beam else configs:
        if beam and n >= beam:
            break
        if add(*cfg):
            frontier.append(cfg)
            n += 1
    while frontier:
        new = []
        for s, okm, crm in frontier:
            for bit, writes, touched, crashed in steps:
                if (crm if crashed else okm) & bit:
                    continue
                s2 = _step(s, writes, touched)
                if s2 is None:
                    continue
                cfg = (s2, okm, crm | bit) if crashed else (s2, okm | bit, crm)
                if beam and n >= beam:
                    continue
                if add(*cfg):
                    new.append(cfg)
                    n += 1
        frontier = new
    return {(s, okm, crm) for (s, okm), cs in kept.items() for crm in cs}
