"""Program registry: what a compiled program can be asked after it is
built.

Whoever holds a `jax.stages.Compiled` leaves it here under the name
the profiler gives its launches (`jit_decode_fn`: the "XLA Modules"
events of a device profile read `jit_decode_fn(<hash>)`). The registry
is process-global and keeps the LAST executable per name, so it still
answers after the engine that built the program is gone. A `Compiled`
holds the executable and its argument layouts, no device array, so
keeping it does not keep weights or KV pools alive.

Registering is one dict insert per program built and nothing per
launch. The two questions below cost something and are answered only
when asked (`as_text()` of a 48-layer decode program is ~0.4 MB of
HLO), once per executable:

  * `op_scopes(name)`: {HLO instruction name: JAX name stack}, from
    the `metadata={op_name="..."}` of the optimized HLO. A fusion
    carries its root's name stack. This is the join between a device
    profile, whose "XLA Ops" events are named by instruction
    (`%copy.27`), and the `jax.named_scope` regions of the program
    (`jit(decode_fn)/layers/while/body/closed_call/kv_gather/...`).
    An instruction the compiler inserted has no `op_name`; it takes
    its first operand's, and is absent from the map if that has none.
  * `memory(name)`: the compiler's own byte counts, including the
    program's temporaries, which `memory_stats()["peak_bytes_in_use"]`
    of an idle device does not show.
  * `relaid(name)`: the bytes of weights the program writes only to
    hold them in another arrangement: every `copy` of a slice of a
    scan's stacked operands (its name stack ends in
    `while/body/dynamic_slice`) and of a leaf of the argument `params`
    itself (a stack of one layer, whose loop the compiler inlines).
    Each instruction counts once, so one in a scan's body is the bytes
    of ONE layer. 0 where the products read the weights where they lie
    (`models/brumby.py::head_projection`).
"""

import math
import re

MEMORY_FIELDS = ("argument", "output", "alias", "temp", "generated_code")

# `  ROOT %copy.27 = bf16[..] copy(..), metadata={op_name="jit(f)/.." ..}`
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$', re.M)
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]+)"')
_OPERAND = re.compile(r'%([\w.\-]+)')     # shapes hold no `%`
# `%copy.177 = bf16[1,4096,12288]{1,2,0:T(8,128)(2,1)S(1)} copy(%..), ..`
_COPY = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* '
                   r'copy\(', re.M)
_SLICED = "while/body/dynamic_slice"

_programs = {}        # name -> _Program


class _Program:
    __slots__ = ("compiled", "compile_seconds", "scopes", "memory",
                 "relaid")

    def __init__(self, compiled, compile_seconds):
        self.compiled = compiled
        self.compile_seconds = compile_seconds
        self.scopes = None
        self.memory = None
        self.relaid = None


def register(name, compiled, compile_seconds=None):
    """Keep `compiled` as the program `name` (replacing an earlier
    one: its cached answers go with it)."""
    _programs[name] = _Program(compiled, compile_seconds)


def parse_op_scopes(hlo_text):
    """{instruction name: name stack} of `hlo_text` (names are unique
    within a module). An instruction without an `op_name` (the
    compiler inserted it: a `copy` for a layout or a buffer) takes its
    first operand's, so the copy of a `while`'s result belongs to
    what the `while` belongs to; with no named operand it is left
    out."""
    scopes = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        found = _OP_NAME.search(rest)
        if found:
            scopes[name] = found.group(1)
            continue
        operand = _OPERAND.search(rest)
        if operand and operand.group(1) in scopes:
            scopes[name] = scopes[operand.group(1)]
    return scopes


def parse_relaid(hlo_text):
    """Bytes written by the `copy` instructions of `hlo_text` that
    re-lay a weight: those whose `op_name` ends in
    `while/body/dynamic_slice` (a layer's slice of a scan's stacked
    operands) or is a leaf's of the program's argument `params`
    (`params['dense']['wq']`), by `parse_op_scopes`: a copy the
    compiler inserted is its operand's. A copy-start/copy-done pair
    (a prefetch) is not one, nor a copy inside a fusion of another
    name."""
    scopes = parse_op_scopes(hlo_text)
    total = 0
    for name, dtype, dims in _COPY.findall(hlo_text):
        stack = scopes.get(name, "")
        if stack.endswith(_SLICED) or stack.startswith("params["):
            width = re.search(r"\d+$", dtype)
            # pred and the 4-bit types round up to a byte an element
            total += math.prod(map(int, filter(None, dims.split(",")))) * \
                max(int(width.group()) if width else 8, 8) // 8
    return total


def op_scopes(name):
    """{HLO instruction name: JAX name stack} of the program `name`,
    or None if no such program is registered."""
    prog = _programs.get(name)
    if prog is None:
        return None
    if prog.scopes is None:
        prog.scopes = parse_op_scopes(prog.compiled.as_text())
    return prog.scopes


def memory(name):
    """{"argument", "output", "alias", "temp", "generated_code"} in
    bytes as the compiler reports them, or None if no such program is
    registered or the backend gives no analysis."""
    prog = _programs.get(name)
    if prog is None:
        return None
    if prog.memory is None:
        stats = prog.compiled.memory_analysis()
        if stats is None:
            return None
        prog.memory = {f: int(getattr(stats, f + "_size_in_bytes"))
                       for f in MEMORY_FIELDS}
    return prog.memory


def relaid(name):
    """`parse_relaid` of the program `name`, or None if no such
    program is registered."""
    prog = _programs.get(name)
    if prog is None:
        return None
    if prog.relaid is None:
        prog.relaid = parse_relaid(prog.compiled.as_text())
    return prog.relaid


def programs():
    """The table an operator prints: one row per registered program
    with the compiler's byte counts, the seconds its compile (or its
    load from the compilation cache) took and the bytes of weights it
    re-lays (`relaid`)."""
    return [dict(memory(name) or dict.fromkeys(MEMORY_FIELDS), name=name,
                 compile_seconds=prog.compile_seconds, relaid=relaid(name))
            for name, prog in sorted(_programs.items())]
