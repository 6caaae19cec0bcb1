"""Small shared helpers (single home for cross-module utilities)."""


def pair(v):
    """Normalize an int-or-2-seq into a (h, w) tuple."""
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def format_callstack(frames, prefix="    "):
    """Render Operator.callstack frames ((filename, lineno, function)
    triples, innermost first) traceback-style. Source lines load lazily
    via linecache — recording stays cheap, formatting pays only when an
    error/diagnostic is actually shown."""
    import linecache
    lines = []
    for filename, lineno, func in frames:
        lines.append('%sFile "%s", line %d, in %s'
                     % (prefix, filename, lineno, func))
        src = linecache.getline(filename, lineno).strip()
        if src:
            lines.append(prefix + "  " + src)
    return "\n".join(lines)


def find_var(program, name):
    """Look a var up across all blocks of a program (None if absent)."""
    for block in program.blocks:
        if name in block.vars:
            return block.vars[name]
    return None


def device_fetch_barrier(out):
    """Wait until every array in `out` (FetchHandles unwrapped) has been
    computed: the end of a timing loop. chip_smoke.py phase E checks on
    the chip that this agrees with a device->host fetch."""
    import jax
    from .executor import FetchHandle
    jax.block_until_ready([
        leaf.array if isinstance(leaf, FetchHandle) else leaf
        for leaf in jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: isinstance(x, FetchHandle))])


def fsync_dir(path):
    """fsync a directory fd — the step that makes a just-renamed entry
    durable against power loss. Shared by checkpoint/snapshot.py and
    core/compile_cache.py so the crash-safety discipline has ONE
    implementation."""
    import os
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def write_bytes_fsync(path, data):
    """Write + flush + fsync one file (the durability sibling of
    fsync_dir; see its note on sharing)."""
    import os
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


def atomic_write_json(path, obj, fsync=False, **dump_kw):
    """Publish a JSON document atomically: serialize, write to a tmp
    sibling named by pid and thread, one os.replace. Readers never see a
    torn document, also when two threads of one process publish the same
    path (a heartbeat's beat thread and an update() from the training
    loop: with one tmp file a process, the first replace would publish
    the second writer's half-written bytes). fsync=True adds the
    write_bytes_fsync durability step for documents that must survive
    power loss (the cluster plan); liveness signals (heartbeats, fired
    every fraction of a second) skip it. ONE implementation for every
    tmp+replace JSON writer so the atomicity discipline can't drift per
    copy."""
    import json
    import os
    import threading
    data = json.dumps(obj, **dump_kw).encode("utf-8")
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(), threading.get_ident())
    if fsync:
        write_bytes_fsync(tmp, data)
    else:
        with open(tmp, "wb") as f:
            f.write(data)
    os.replace(tmp, path)
