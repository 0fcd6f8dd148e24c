"""Op and history model — the substrate shared by the runtime and the checkers.

Mirrors the reference's operation model: a history is a flat sequence of op
maps, where each logical operation appears (up to) twice — once as an
``invoke`` entry when a process begins it, and once as a completion entry
(``ok`` / ``fail`` / ``info``) when the process hears back.  (Reference:
knossos op predicates used throughout jepsen/src/jepsen/checker.clj:157-159,
and history indexing at jepsen/src/jepsen/core.clj:223.)

Completion semantics (these leak into every checker, so they are fixed here):

- ``ok``    — the operation definitely took effect, exactly once, at some
              instant between its invocation and its completion.
- ``fail``  — the operation definitely did NOT take effect.
- ``info``  — indeterminate: the op may or may not have taken effect, at any
              instant from its invocation onward (the process crashed; the
              reference converts worker exceptions into ``:info`` ops at
              jepsen/src/jepsen/generator/interpreter.clj:142-157).

In addition to the friendly Python-object view (:class:`Op`, :class:`History`)
this module provides the struct-of-arrays encoding (:class:`HistorySOA`) that
the TPU checkers consume: fixed-width int32 columns, with model-specific value
encoding delegated to the model (see jepsen_tpu.models.base).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Op
# ---------------------------------------------------------------------------

INVOKE = "invoke"
OK = "ok"
FAIL = "fail"
INFO = "info"

TYPES = (INVOKE, OK, FAIL, INFO)
TYPE_CODE = {t: i for i, t in enumerate(TYPES)}

# Reserved logical process for the nemesis, mirroring the reference where the
# nemesis runs as the :nemesis process (jepsen/src/jepsen/generator.clj:1105).
NEMESIS = "nemesis"


_OP_FIELDS = frozenset(
    ("process", "type", "f", "value", "time", "index", "error", "extra"))


@dataclass
class Op:
    """One history entry.

    ``value`` is free-form (model-specific); ``process`` is an int for client
    processes or the string ``"nemesis"``; ``time`` is nanoseconds since test
    start (relative clock, like util/relative-time in the reference).
    """

    process: Any
    type: str
    f: Any
    value: Any = None
    time: Optional[int] = None
    index: Optional[int] = None
    error: Any = None
    extra: Dict[str, Any] = field(default_factory=dict)

    # -- predicates (knossos.op parity: op/ok? fail? info? invoke?) --------
    @property
    def invoke_(self) -> bool:
        return self.type == INVOKE

    @property
    def ok_(self) -> bool:
        return self.type == OK

    @property
    def fail_(self) -> bool:
        return self.type == FAIL

    @property
    def info_(self) -> bool:
        return self.type == INFO

    def with_(self, **kw) -> "Op":
        # hand-rolled copy: dataclasses.replace re-runs __init__ and is
        # the scheduler's hottest call (hundreds of thousands per run)
        extra = kw.pop("extra", None)
        if not kw.keys() <= _OP_FIELDS:
            raise TypeError(
                f"unknown Op fields: {sorted(kw.keys() - _OP_FIELDS)}")
        new = object.__new__(Op)
        d = self.__dict__.copy()
        d.update(kw)
        if extra:
            d["extra"] = {**self.extra, **extra}
        new.__dict__ = d
        return new

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "index": self.index,
            "type": self.type,
            "process": self.process,
            "f": self.f,
            "value": self.value,
            "time": self.time,
        }
        if self.error is not None:
            d["error"] = self.error
        d.update(self.extra)
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Op":
        known = {"index", "type", "process", "f", "value", "time", "error"}
        return cls(
            process=d.get("process"),
            type=d.get("type"),
            f=d.get("f"),
            value=d.get("value"),
            time=d.get("time"),
            index=d.get("index"),
            error=d.get("error"),
            extra={k: v for k, v in d.items() if k not in known},
        )

    def __repr__(self) -> str:  # compact, jepsen-log-style
        return (f"Op({self.index} {self.process} :{self.type} :{self.f} "
                f"{self.value!r}" + (f" err={self.error!r}" if self.error else "") + ")")


def invoke_op(process, f, value=None, **kw) -> Op:
    return Op(process=process, type=INVOKE, f=f, value=value, **kw)


# ---------------------------------------------------------------------------
# History
# ---------------------------------------------------------------------------


class History(Sequence):
    """An indexed sequence of :class:`Op` with pairing transforms.

    Construction assigns ``index`` to each op if absent (parity with
    history/index used at jepsen/src/jepsen/core.clj:223).
    """

    def __init__(self, ops: Iterable[Any], reindex: bool = False):
        self.ops: List[Op] = []
        for i, o in enumerate(ops):
            if isinstance(o, dict):
                o = Op.from_dict(o)
            if reindex or o.index is None:
                o = o.with_(index=i)
            self.ops.append(o)
        self._pairs: Optional[np.ndarray] = None

    @classmethod
    def adopt(cls, ops: List[Op]) -> "History":
        """A history that takes ``ops`` as its own list: every element an
        :class:`Op` whose ``index`` is already its position, so nothing is
        copied or checked."""
        h = object.__new__(cls)
        h.ops = ops
        h._pairs = None
        return h

    # -- Sequence protocol -------------------------------------------------
    def __len__(self) -> int:
        return len(self.ops)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return History(self.ops[i])
        return self.ops[i]

    def __iter__(self) -> Iterator[Op]:
        return iter(self.ops)

    def __eq__(self, other):
        return isinstance(other, History) and self.ops == getattr(other, "ops", None)

    def __repr__(self):
        return f"History<{len(self)} ops>"

    # -- transforms --------------------------------------------------------
    def pair_index(self) -> np.ndarray:
        """pair_index[i] = index of i's partner entry, or -1 (unmatched).

        An invoke's partner is its completion (same process, next entry);
        a completion's partner is its invoke.  Info ops emitted by the
        nemesis (no invoke) pair to -1.
        """
        if self._pairs is not None:
            return self._pairs
        pairs = np.full(len(self.ops), -1, dtype=np.int64)
        open_invokes: Dict[Any, int] = {}
        for i, op in enumerate(self.ops):
            if op.type == INVOKE:
                open_invokes[op.process] = i
            elif op.type in (OK, FAIL, INFO):
                j = open_invokes.pop(op.process, None)
                if j is not None:
                    pairs[i] = j
                    pairs[j] = i
        self._pairs = pairs
        return pairs

    def invocations(self) -> List[Op]:
        return [o for o in self.ops if o.type == INVOKE]

    def completions(self) -> List[Op]:
        return [o for o in self.ops if o.type in (OK, FAIL, INFO)]

    def oks(self) -> List[Op]:
        return [o for o in self.ops if o.type == OK]

    def client_ops(self) -> "History":
        return History([o for o in self.ops if o.process != NEMESIS])

    def complete(self) -> "History":
        """Knossos history/complete parity: an OK completion's value is
        adopted by its invocation unconditionally (knossos history/complete
        assoc's the completion :value onto the invoke), so reads invoked with
        structured placeholders like [[k, None], ...] step the model with the
        observed value, not the placeholder. Unmatched invokes stay open
        (treated as concurrent-to-the-end by the checkers)."""
        pairs = self.pair_index()
        out = []
        for i, op in enumerate(self.ops):
            if op.type == INVOKE:
                j = pairs[i]
                if j >= 0:
                    comp = self.ops[j]
                    if comp.type == OK and comp.value is not None:
                        op = op.with_(value=comp.value)
            out.append(op)
        return History(out)

    def pairs(self) -> List[Tuple[Op, Optional[Op]]]:
        """[(invoke, completion-or-None), ...] in invocation order."""
        idx = self.pair_index()
        out = []
        for i, op in enumerate(self.ops):
            if op.type == INVOKE:
                j = idx[i]
                out.append((op, self.ops[j] if j >= 0 else None))
        return out

    # -- I/O ---------------------------------------------------------------
    def to_jsonl(self, path: str) -> None:
        # Atomic publish (atomic_io): the history is the one artifact a
        # crashed analysis re-runs from; a torn write must never shadow a
        # previously complete copy.
        from jepsen_tpu.atomic_io import atomic_write

        def dump(f):
            for op in self.ops:
                f.write(json.dumps(op.to_dict(), default=str) + "\n")

        atomic_write(path, dump)

    @classmethod
    def from_jsonl(cls, path: str) -> "History":
        with open(path) as f:
            return cls([json.loads(line) for line in f if line.strip()])

    @classmethod
    def from_edn_file(cls, path: str) -> "History":
        """Read a reference-format history.edn (one op map per line, or one
        top-level vector)."""
        with open(path) as f:
            return cls.from_edn(f.read())

    @classmethod
    def from_edn(cls, text: str) -> "History":
        data = parse_edn_stream(text)
        if len(data) == 1 and isinstance(data[0], list):
            data = data[0]
        return cls([_edn_map_to_op(m) for m in data])


def _edn_map_to_op(m: Dict[str, Any]) -> Op:
    return Op.from_dict(m)


# ---------------------------------------------------------------------------
# Minimal EDN reader — enough for jepsen history files
# ---------------------------------------------------------------------------
# The reference persists histories as EDN (jepsen/src/jepsen/store.clj) using
# maps, vectors, keywords, strings, numbers, nil, booleans.  Keywords are
# decoded to plain strings ("read", not ":read"); map keys likewise.


class _EdnReader:
    def __init__(self, text: str):
        self.t = text
        self.i = 0
        self.n = len(text)

    def _skip_ws(self):
        while self.i < self.n:
            c = self.t[self.i]
            if c in " \t\r\n,":
                self.i += 1
            elif c == ";":  # comment to EOL
                while self.i < self.n and self.t[self.i] != "\n":
                    self.i += 1
            else:
                break

    def at_end(self) -> bool:
        self._skip_ws()
        return self.i >= self.n

    def read(self):
        self._skip_ws()
        if self.i >= self.n:
            raise ValueError("EDN: unexpected end of input")
        c = self.t[self.i]
        if c == "{":
            return self._read_map()
        if c == "[" or c == "(":
            return self._read_seq("]" if c == "[" else ")")
        if c == "#":
            return self._read_dispatch()
        if c == '"':
            return self._read_string()
        if c == ":":
            return self._read_keyword()
        return self._read_atom()

    def _read_map(self):
        self.i += 1  # {
        out = {}
        while True:
            self._skip_ws()
            if self.i < self.n and self.t[self.i] == "}":
                self.i += 1
                return out
            k = self.read()
            v = self.read()
            out[k] = v

    def _read_seq(self, close):
        self.i += 1
        out = []
        while True:
            self._skip_ws()
            if self.i < self.n and self.t[self.i] == close:
                self.i += 1
                return out
            out.append(self.read())

    def _read_dispatch(self):
        # #{...} sets, #inst "..." dates, tagged literals -> best effort
        self.i += 1
        c = self.t[self.i] if self.i < self.n else ""
        if c == "{":
            return set_safe(self._read_seq("}"))
        # tagged literal: read symbol then value, keep the value
        self._read_atom()
        return self.read()

    def _read_string(self):
        self.i += 1
        out = []
        while self.i < self.n:
            c = self.t[self.i]
            if c == "\\":
                nxt = self.t[self.i + 1]
                out.append({"n": "\n", "t": "\t", "r": "\r"}.get(nxt, nxt))
                self.i += 2
            elif c == '"':
                self.i += 1
                return "".join(out)
            else:
                out.append(c)
                self.i += 1
        raise ValueError("EDN: unterminated string")

    def _read_keyword(self):
        self.i += 1  # :
        return self._read_symbol_text()

    def _read_symbol_text(self) -> str:
        start = self.i
        while self.i < self.n and self.t[self.i] not in ' \t\r\n,()[]{}";':
            self.i += 1
        return self.t[start:self.i]

    def _read_atom(self):
        tok = self._read_symbol_text()
        if tok == "nil":
            return None
        if tok == "true":
            return True
        if tok == "false":
            return False
        try:
            if any(ch in tok for ch in ".eEM") and not tok.startswith("0x"):
                if tok.endswith("M"):
                    return float(tok[:-1])
                return float(tok)
            if tok.endswith("N"):
                return int(tok[:-1])
            return int(tok, 0)
        except ValueError:
            return tok  # bare symbol


def set_safe(items):
    try:
        return set(items)
    except TypeError:
        return items


def parse_edn(text: str):
    return _EdnReader(text).read()


def parse_edn_stream(text: str) -> List[Any]:
    r = _EdnReader(text)
    out = []
    while not r.at_end():
        out.append(r.read())
    return out


# ---------------------------------------------------------------------------
# Struct-of-arrays device encoding
# ---------------------------------------------------------------------------


@dataclass
class HistorySOA:
    """Fixed-width column view of a history for device consumption.

    Columns (all int32, length = #entries):
      type    — TYPE_CODE
      process — client process id (nemesis = -1)
      f       — model-assigned function code
      a, b    — model-encoded value operands
      pair    — partner entry index (-1 if none)
      time    — int64 nanoseconds (kept host-side; not shipped to device)
    """

    type: np.ndarray
    process: np.ndarray
    f: np.ndarray
    a: np.ndarray
    b: np.ndarray
    pair: np.ndarray
    time: np.ndarray

    def __len__(self):
        return len(self.type)


def encode_soa(history: History, encode_op: Callable[[Op], Tuple[int, int, int]]) -> HistorySOA:
    """Encode a history with a model-supplied ``encode_op(op) -> (f, a, b)``.

    ``encode_op`` sees the *completed* view of each op (invoke values filled
    from completions), so reads carry their observed value on both entries.
    """
    h = history.complete()
    n = len(h)
    typ = np.empty(n, np.int32)
    proc = np.empty(n, np.int32)
    fc = np.empty(n, np.int32)
    av = np.empty(n, np.int32)
    bv = np.empty(n, np.int32)
    tm = np.zeros(n, np.int64)
    for i, op in enumerate(h):
        typ[i] = TYPE_CODE[op.type]
        proc[i] = -1 if op.process == NEMESIS else int(op.process)
        f, a, b = encode_op(op)
        fc[i], av[i], bv[i] = f, a, b
        tm[i] = op.time or 0
    return HistorySOA(type=typ, process=proc, f=fc, a=av, b=bv,
                      pair=h.pair_index().astype(np.int32), time=tm)
