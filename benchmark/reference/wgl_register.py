"""Plain reference: linearizability of one CAS-register history.

The straightforward configuration search of Wing & Gong as knossos runs it
(Lowe's just-in-time form): walk the history in order; a configuration is
(register value, the set of pending ops already linearized); when an op
returns ``ok``, close the set of configurations under linearizing any
pending op, keep those that linearized the returning op, and go on.  The
history is linearizable iff a configuration survives to the end; otherwise
the refuting op is the one whose return left none.

Independent of the program: imports nothing of it, reads the benchmark's own
plain records.  Semantics are the published ones (knossos.model/cas-register,
knossos.history/complete, jepsen's op types):

- ``ok``   took effect exactly once between its invoke and its completion;
- ``fail`` did not take effect: the pair is dropped;
- ``info`` (crashed) may take effect at any time from its invoke on, or
  never: it stays pending for ever and is never required;
- a crashed read constrains nothing and is dropped;
- an ``ok`` completion's value is its invocation's (a read's observed value);
- a read of ``None`` is legal in any state; the register starts at ``None``.

Two sound prunings keep it within seconds at 10,000 ops.  Neither changes a
verdict: (1) a crashed CAS whose expected value no op of the history can
ever write cannot take effect and is dropped like a crashed read; (2) of two
configurations equal but for the crashed ops they linearized, the one that
linearized a subset can do everything the other can (a crashed op is never
required), so only minimal sets are kept.

``beam`` is the control's handle, not the reference's: with ``beam=k`` the
closure keeps at most ``k`` configurations and calls a history refuted when
they die out, the truncated search that answers ``false`` where an honest
degraded search has to answer ``unknown``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

READ, WRITE, CAS = 0, 1, 2
_F = {"read": READ, "r": READ, "write": WRITE, "w": WRITE, "cas": CAS}


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
    ops: List[Tuple[int, Any, Any, bool, int]] = []   # f, a, b, crashed, pos
    enter_at: Dict[int, int] = {}
    return_at: Dict[int, int] = {}
    writable = {None}
    for inv, comp in _pair(records):
        o = records[inv]
        f = _F[o.f]
        if f == WRITE:
            writable.add(o.value)
        elif f == CAS and o.value is not None:
            writable.add(o.value[1])
    for inv, comp in _pair(records):
        o = records[inv]
        ctype = records[comp].type if comp is not None else "info"
        if ctype == "fail" or (ctype == "info" and info_as_fail):
            continue
        f = _F[o.f]
        value = o.value
        if ctype == "ok" and records[comp].value is not None:
            value = records[comp].value
        crashed = ctype == "info"
        if crashed and f == READ:
            continue
        a, b = (value[0], value[1]) if f == CAS else (value, None)
        if crashed and f == CAS and a not in writable:
            continue
        enter_at[inv] = len(ops)
        if not crashed:
            return_at[comp] = len(ops)
        ops.append((f, a, b, crashed, inv))

    # -- the search -------------------------------------------------------
    # pending: op id -> bit; a configuration is (value, ok-bits, crash-bits)
    bit_of: Dict[int, int] = {}
    free_bits: List[int] = []
    next_bit = 0
    pending: List[int] = []
    configs = {(None, 0, 0)}
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
        steps = [(bit_of[i],) + ops[i][:4] for i in pending]
        configs = _closure(configs, steps, beam)
        rbit = bit_of[ret]
        configs = {(v, okm & ~rbit, crm) for (v, okm, crm) in configs
                   if okm & rbit}
        if not configs:
            return {"valid": False, "op_index": ops[ret][4]}
        pending.remove(ret)
        free_bits.append(bit_of.pop(ret))
    return {"valid": True}


def _closure(configs, steps, beam):
    """Every configuration reachable by linearizing pending ops, minimal in
    the crashed ops used."""
    kept: Dict[Tuple[Any, int], List[int]] = {}

    def add(v, okm, crm) -> bool:
        got = kept.get((v, okm))
        if got is None:
            kept[(v, okm)] = [crm]
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
        for v, okm, crm in frontier:
            for bit, f, a, b, crashed in steps:
                if (crm if crashed else okm) & bit:
                    continue
                if f == READ:
                    if a is not None and a != v:
                        continue
                    v2 = v
                elif f == WRITE:
                    v2 = a
                else:
                    if v != a:
                        continue
                    v2 = b
                cfg = (v2, okm, crm | bit) if crashed else (v2, okm | bit, crm)
                if beam and n >= beam:
                    continue
                if add(*cfg):
                    new.append(cfg)
                    n += 1
        frontier = new
    return {(v, okm, crm) for (v, okm), cs in kept.items() for crm in cs}
