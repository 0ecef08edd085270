"""Which part of the program each operation of a device profile belongs to.

A TPU profile names an operation by its HLO instruction (`%fusion.12`,
`%ragged_paged_attention.3`) on the "XLA Ops" line, inside an event of
its program on the "XLA Modules" line (`jit_ragged_step_p128`), and
carries no `jax.named_scope`.  The compiled text does: every instruction
XLA kept from the program holds ``metadata={op_name="jit(ragged_step_p128)
/attention/window/dot_general"}``.  `device_op_scopes()` is the join key:

    {module name: {instruction name: scope path}}

the scope path being the op_name without the program, without the
primitive and without the `jit(...)` of the library functions it passed
through (``attention/window``); "" for an instruction of the program
outside every scope.  An instruction XLA made itself (a prefetch of a
weight, a copy it scheduled) has no op_name, and one that a library
lowered as a function of its own keeps that function's op_name alone
(`reduce_window_sum` of a cumsum, `ragged-dot-none` of XLA:TPU's grouped
product): such an instruction takes the scope of its first user that
has one, the part of the program it serves.  Instructions inside fusions
are not on a profile's line and are left out.

`CompiledModelCache` registers every executable it compiles, which
keeps a weak reference and reads nothing: the text is read, once a
program, when the profiler stops or when someone asks here, and kept
as strings, so the map outlives the engine that compiled it.
"""
import re
import threading
import weakref

_programs = []                    # weak references, not read yet
_scopes = {}                      # module name -> {instruction: path}
_lock = threading.Lock()

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_FUSED = re.compile(r" fusion\(.*?, calls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')


def register_program(compiled):
    """Keep `compiled` (a `jax.stages.Compiled`) to be read later."""
    with _lock:
        _programs.append(weakref.ref(compiled))


def scope_path(op_name):
    """``jit(p)/experts/jit(silu)/logistic`` -> ``experts``; None for an
    op_name that does not begin at the program (``ragged-dot-none``)."""
    parts = op_name.split("/")
    if not parts[0].endswith(")"):
        return None
    return "/".join(p for p in parts[1:-1] if "(" not in p)


def _scopes_of(instructions):
    """{name: path} of one computation's [(name, path or None,
    operands)] in text order, where an operand comes before its users:
    walked backwards, an instruction without a path of its own takes
    that of its first user that has one."""
    scopes, first_user = {}, {}
    for name, path, operands in reversed(instructions):
        if path is None:
            path = first_user.get(name)
        if path is None:
            continue
        scopes[name] = path
        for operand in operands:
            first_user[operand] = path
    return scopes


def read_module(text):
    """(module name, {instruction: scope path}) of one compiled text."""
    found = _MODULE.search(text)
    fused = set(_FUSED.findall(text))
    scopes, block, skip = {}, [], True
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            block, skip = [], head.group(1) in fused
            continue
        if line == "}":
            scopes.update(_scopes_of(block))
            block = []
            continue
        m = None if skip else _INSTRUCTION.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        call = line[m.end():].split("), ", 1)[0]
        block.append((m.group(1), op and scope_path(op.group(1)),
                      _OPERAND.findall(call)))
    return (found.group(1) if found else ""), scopes


def device_op_scopes():
    """``{module name: {instruction name: scope path}}`` of every program
    registered in this process and still held, read now where not read
    before; of two programs of one name (two engines of one shape), the
    later one registered."""
    with _lock:
        fresh = [ref() for ref in _programs]
        _programs.clear()
        for compiled in fresh:
            if compiled is not None:
                module, scopes = read_module(compiled.as_text())
                _scopes[module] = scopes
        return dict(_scopes)
