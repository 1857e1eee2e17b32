"""Attribution of a train step's device ops to the program's own names.

The program wraps its work in ``jax.named_scope`` (``frontend``, ``embed``,
``blocks``, ``attn/proj``, ``attn/core``, ``mlp``, ``norm``, ``loss``,
``optimizer``), and XLA keeps the name stack in each instruction's
``metadata={op_name=...}``, fusions included. JAX's own transforms mark the
phase in the same string: the forward runs under ``jvp(...)``, the backward
under ``transpose(jvp(...))``, remat's second forward under
``.../rematted_computation/...``; the flash attention's custom VJP names
its backward ``bwd``, since JAX traces it outside the transpose.

So every device op gets a label ``<phase>/<scope>``: phase ``fwd``,
``remat``, ``bwd`` or ``opt``, scope the innermost name of the vocabulary.
A fusion whose op_name joins several names with ``;``, or that has none
and takes the names of the instructions it fuses, gets their common scope
(``attn`` for ``attn/proj`` with ``attn/core``, ``blocks`` for ``mlp`` with
``norm``; the first name's where they share none) and the phase of its
first name, which for a fused computation is its root's. An op with no
name stack (no metadata, or a bare name such as ``convert.7`` that XLA
gives the collectives it adds after an instruction of its own) is
``unscoped``; one whose name stack holds no scope is ``<phase>/unscoped``.

The op_names come from the HLO of the module that ran: the profile embeds
each program's ``HloProto`` (read here from the ``.xplane.pb`` with a small
protobuf reader: JAX ships no module for the XSpace message), and a
compiled program's ``as_text()`` gives the same on the CPU.
"""
from __future__ import annotations

import functools
import gzip
import re
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from .common import OUT, log
from .trace import DeviceOp, module_ops, op_kind, parse_op, self_ns

SCOPES = ("frontend", "embed", "blocks", "attn", "attn/proj", "attn/core",
          "mlp", "norm", "loss", "optimizer")
PHASES = ("fwd", "remat", "bwd", "opt")
UNSCOPED = "unscoped"
REMAT = "rematted_computation"

_TOP = frozenset(s for s in SCOPES if "/" not in s)
_WRAP = re.compile(r"([\w\-]+)\((.*)\)")
_FUNCTIONS = ("jit", "pjit")            # wrap a function's name, not scopes


def _split(s: str) -> List[str]:
    """``s`` cut at each ``/`` outside parentheses."""
    out, depth, cur = [], 0, []
    for ch in s:
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return out


@functools.lru_cache(maxsize=65536)
def _parse(op_name: str) -> Tuple[Tuple[str, ...], frozenset]:
    """The scope names of one op_name (no ``;``), outermost first, and the
    transforms around them (``jvp``, ``transpose``, ...). The last part
    names the primitive, and a ``jit(f)`` wraps a function's name: neither
    is a scope."""
    names: List[str] = []
    transforms = set()

    def walk(parts: List[str]) -> None:
        for part in parts:
            m = _WRAP.fullmatch(part)
            if m is None:
                names.append(part)
                continue
            transforms.add(m.group(1))
            if m.group(1) not in _FUNCTIONS:
                walk(_split(m.group(2)))

    walk(_split(op_name)[:-1])
    return tuple(names), frozenset(transforms)


def scope_path(op_name: str) -> List[str]:
    """The vocabulary's scopes in one op_name (no ``;``), outermost first:
    ``attn`` followed by ``proj`` or ``core`` adds both ``attn`` and
    ``attn/proj`` or ``attn/core``."""
    path: List[str] = []
    names = _parse(op_name)[0]
    for i, t in enumerate(names):
        if t in _TOP:
            path.append(t)
        elif t in ("proj", "core") and i and names[i - 1] == "attn":
            path.append(f"attn/{t}")
    return path


def phase_of(op_name: str) -> str:
    """``remat`` under remat's recompute, ``bwd`` under a transpose or a
    custom VJP's backward, ``opt`` under the optimizer, else ``fwd``
    (the forward, and what it computes from constants alone)."""
    names, transforms = _parse(op_name)
    if REMAT in names:
        return "remat"
    if "transpose" in transforms or "bwd" in names:
        return "bwd"
    if "optimizer" in names:
        return "opt"
    return "fwd"


def label(names: Sequence[str]) -> str:
    """``<phase>/<scope>`` of an op from its op_names (first name first).
    Only a name stack counts: a bare name (an argument's, or one XLA gives
    after an instruction of its own) tells neither scope nor phase."""
    names = [n for n in names if "/" in n]
    if not names:
        return UNSCOPED
    paths = [p for p in map(scope_path, names) if p]
    common: List[str] = []
    for level in zip(*paths):
        if any(s != level[0] for s in level):
            break
        common.append(level[0])
    scope = common or (paths[0] if paths else [UNSCOPED])
    return f"{phase_of(names[0])}/{scope[-1]}"


def is_scoped(lab: str) -> bool:
    return not lab.endswith(UNSCOPED)


# ------------------------------------------------------------- HLO modules

@dataclass
class Instr:
    name: str
    opcode: str
    op_name: str
    calls: List[str] = field(default_factory=list)   # computations called


@dataclass
class HloModule:
    """An optimized HLO module: its instructions by name (names are unique
    in a module) and each computation's instructions, root first."""
    name: str
    instrs: Dict[str, Instr]
    comps: Dict[str, List[str]]
    entry: str = ""

    def names_of(self, instr: Instr) -> List[str]:
        """The op_names that stand for an instruction: its own, split at
        ``;``, or for a fusion whose own names hold no scope (XLA's passes
        leave some fusions none, or their own name) those of what it
        fuses, root first."""
        own = instr.op_name.split(";") if instr.op_name else []
        if instr.opcode != "fusion" or any(map(scope_path, own)):
            return own
        fused: List[str] = []
        for comp in instr.calls:
            for n in self.comps.get(comp, []):
                fused += self.names_of(self.instrs[n])
        return fused if any(map(scope_path, fused)) else own or fused

    def labels(self) -> Dict[str, str]:
        return {n: label(self.names_of(i)) for n, i in self.instrs.items()}

    def executed(self) -> List[Instr]:
        """Instructions that run as device ops: those of the entry
        computation and of the computations control flow calls, not those
        a fusion or a reduction holds."""
        seen, todo, out = set(), [self.entry], []
        while todo:
            comp = todo.pop()
            if comp in seen or comp not in self.comps:
                continue
            seen.add(comp)
            for n in self.comps[comp]:
                ins = self.instrs[n]
                out.append(ins)
                if ins.opcode in ("while", "conditional", "call"):
                    todo += ins.calls
        return out


_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition)=%([\w.\-]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def parse_hlo_text(text: str) -> HloModule:
    """An optimized module from its HLO text (``compiled.as_text()``)."""
    first = text.split("\n", 1)[0]
    name = first.split()[1].rstrip(",") if first.startswith("HloModule") \
        else ""
    mod = HloModule(name, {}, {})
    comp: Optional[str] = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(2)
            mod.comps[comp] = []
            if m.group(1):
                mod.entry = comp
            continue
        s = line.strip()
        if comp is None or not s.startswith(("%", "ROOT %")):
            continue
        root = s.startswith("ROOT ")
        iname, opcode = parse_op(s[5:] if root else s)
        calls: List[str] = []
        for a, b in _CALLED.findall(s):
            calls += [a] if a else [c.strip().lstrip("%")
                                    for c in b.split(",") if c.strip()]
        m = _OP_NAME.search(s)
        op_name = m.group(1).replace('\\"', '"') if m else ""
        mod.instrs[iname] = Instr(iname, opcode, op_name, calls)
        if root:
            mod.comps[comp].insert(0, iname)
        else:
            mod.comps[comp].append(iname)
    return mod


# protobuf wire format, enough of it to walk an XSpace and an HloProto

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _packed(b) -> List[int]:
    if isinstance(b, int):
        return [b]
    out, i = [], 0
    while i < len(b):
        v, i = _varint(b, i)
        out.append(v)
    return out


def module_from_proto(data: bytes) -> HloModule:
    """An optimized module from a serialized ``HloModuleProto`` (name 1,
    entry_computation_name 2, computations 3; a computation's name 1,
    instructions 2, id 5, root_id 6; an instruction's name 1, opcode 2,
    metadata 7 with op_name 2, id 35, called_computation_ids 38)."""
    name = entry = ""
    raw: List[Tuple[str, int, int, List[dict]]] = []
    for num, v in _fields(data):
        if num == 1:
            name = v.decode()
        elif num == 2:
            entry = v.decode()
        elif num == 3:
            cname, cid, root, instrs = "", 0, 0, []
            for k, w in _fields(v):
                if k == 1:
                    cname = w.decode()
                elif k == 5:
                    cid = w
                elif k == 6:
                    root = w
                elif k == 2:
                    ins = {"calls": []}
                    for f, x in _fields(w):
                        if f == 1:
                            ins["name"] = x.decode()
                        elif f == 2:
                            ins["opcode"] = x.decode()
                        elif f == 35:
                            ins["id"] = x
                        elif f == 38:
                            ins["calls"] += _packed(x)
                        elif f == 7:
                            for g, y in _fields(x):
                                if g == 2:
                                    ins["op_name"] = y.decode()
                    instrs.append(ins)
            raw.append((cname, cid, root, instrs))
    by_id = {cid: cname for cname, cid, _, _ in raw}
    mod = HloModule(name, {}, {}, entry)
    for cname, _, root, instrs in raw:
        order = sorted(instrs, key=lambda d: d.get("id") != root)
        mod.comps[cname] = [d["name"] for d in order]
        for d in instrs:
            mod.instrs[d["name"]] = Instr(
                d["name"], d.get("opcode", ""), d.get("op_name", ""),
                [by_id[c] for c in d["calls"] if c in by_id])
    return mod


METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def modules_from_xplane(path: Path) -> Dict[str, HloModule]:
    """Every program's optimized module the profile embeds, keyed by the
    name its runs carry on the ``XLA Modules`` line."""
    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    out: Dict[str, HloModule] = {}
    for num, plane in _fields(data):                  # XSpace.planes
        if num != 1:
            continue
        pf = list(_fields(plane))
        if not any(k == 2 and v == METADATA_PLANE.encode() for k, v in pf):
            continue
        stat_names = {}                               # XPlane.stat_metadata
        for k, v in pf:
            if k == 5:
                for kk, sm in _fields(v):
                    if kk == 2:
                        d = dict(_fields(sm))
                        stat_names[d.get(1)] = d.get(2, b"").decode()
        for k, v in pf:                               # XPlane.event_metadata
            if k != 4:
                continue
            for kk, em in _fields(v):
                if kk != 2:
                    continue
                ev = list(_fields(em))
                ev_name = dict(ev).get(2, b"").decode()
                for ek, st in ev:                     # XEventMetadata.stats
                    if ek != 5:
                        continue
                    sd = dict(_fields(st))
                    if stat_names.get(sd.get(1)) == HLO_PROTO_STAT \
                            and 6 in sd:
                        hlo = dict(_fields(sd[6]))    # HloProto.hlo_module
                        if 1 in hlo:
                            out[ev_name] = module_from_proto(hlo[1])
    return out


# ------------------------------------------------------------ attribution

@dataclass
class Attribution:
    """Each traced step's device ops with their self time and label, and
    the steps' program time summed over the chips (the denominator of
    ``collective_exposed_share``)."""
    ops: List[Tuple[DeviceOp, int, str]]
    spent_ns: int
    seconds: float                       # to fetch the HLO and classify

    def share(self, keep) -> Optional[float]:
        """Self time of the ops whose label ``keep`` accepts, over the
        steps' program time, in %."""
        if self.spent_ns <= 0:
            return None
        return 100.0 * sum(t for _, t, lab in self.ops if keep(lab)) \
            / self.spent_ns

    def scoped_share(self) -> Optional[float]:
        """Scoped share of the steps' device self time, in %."""
        total = sum(t for _, t, _ in self.ops)
        if total <= 0:
            return None
        return 100.0 * sum(t for _, t, lab in self.ops if is_scoped(lab)) \
            / total

    def has(self, keep) -> bool:
        return any(keep(lab) for _, _, lab in self.ops)


def latest_xplane(root: Path = OUT / "trace") -> Optional[Path]:
    """The profile the harness's ``Profiler`` wrote last."""
    found = sorted(Path(root).rglob("*.xplane.pb")) if root.exists() else []
    return found[-1] if found else None


def attribute(run, span: str = "bench.train", xplane: Optional[Path] = None,
              modules: Optional[Dict[str, HloModule]] = None
              ) -> Optional[Attribution]:
    """Labels for the ops of every traced call of ``span``, from the HLO
    the profile embeds (or ``modules``, keyed by module name); None without
    a trace, steps or HLO, and None, logged, where the profile cannot be
    read: a metric reader must not fail the run. Kept on the run, so that
    the readers share one pass."""
    if getattr(run, "trace", None) is None:
        return None
    extra = getattr(run, "extra", None)
    if isinstance(extra, dict) and "scopes" in extra:
        return extra["scopes"]
    try:
        att = _attribute(run.trace, span, xplane, modules)
    except Exception as e:              # noqa: BLE001
        log(f"scopes: attribution failed: {e!r}")
        att = None
    if isinstance(extra, dict):
        extra["scopes"] = att
    return att


def _attribute(trace, span: str, xplane: Optional[Path],
               modules: Optional[Dict[str, HloModule]]
               ) -> Optional[Attribution]:
    t0 = time.monotonic()
    if modules is None:
        xplane = xplane or latest_xplane()
        modules = modules_from_xplane(xplane) if xplane else {}
    labels: Dict[str, Dict[str, str]] = {}
    keys = [(o.device, o.start) for o in trace.ops]
    ops: List[Tuple[DeviceOp, int, str]] = []
    spent = 0
    for key, runs in sorted(trace.call_modules.items()):
        if key[0] != span or not runs:
            continue
        of: Dict[int, str] = {}
        for mod in runs:
            spent += mod.end - mod.start
            if mod.name not in labels and mod.name in modules:
                labels[mod.name] = modules[mod.name].labels()
            lab = labels.get(mod.name, {})
            for o in module_ops(trace, mod, keys):
                of[id(o)] = lab.get(o.name, UNSCOPED)
        call_ops = trace.calls.get(key, [])
        ops += [(o, t, of.get(id(o), UNSCOPED))
                for o, t in zip(call_ops, self_ns(call_ops))]
    if not ops or not labels:
        return None
    att = Attribution(ops, spent, time.monotonic() - t0)
    log(f"scopes: the HLO of {sorted(labels)} read and {len(ops)} ops "
        f"labelled in {att.seconds:.3f} s; scoped share of the steps' "
        f"device self time {att.scoped_share()}%")
    log(f"scopes: device_ops by scope "
        f"{scoped_device_ops(att, trace, call=span.split('.', 1)[-1])}")
    return att


def scoped_device_ops(att: Attribution, trace, n: int = 10,
                      call: str = "train") -> List[List[object]]:
    """The ``device_ops`` breakdown with a finer key,
    ``<call>:<phase>/<scope>:<op kind>``: device seconds (self time,
    averaged over the traced chips) of the traced steps' ops, largest
    first."""
    tot: Dict[str, int] = {}
    w0, w1 = trace.window
    for o, t, lab in att.ops:
        if w0 <= o.start < w1:
            k = f"{call}:{lab}:{op_kind(o)}"
            tot[k] = tot.get(k, 0) + t
    nd = max(1, len(trace.devices))
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / nd] for k, v in top]


def remat(lab: str) -> bool:
    return lab.startswith("remat/")


def in_scope(scope: str):
    return lambda lab: lab.split("/", 1)[-1] == scope
