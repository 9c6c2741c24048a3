"""Faults of the ``pnpcoin-mesh4`` configuration, planted in the
program's timed path by ``tiny_run.py`` (a function of this file is named
on its command line)."""
import dataclasses


def _wrap_run_full(alter):
    """``alter(full)`` gives the block's ``FullResult`` after the
    executor made it."""
    import repro.chain.workload as w
    orig = w.run_full

    def patched(jash, **kw):
        return alter(orig(jash, **kw))
    w.run_full = patched


def zeroed_tail():
    """The last quarter of the results reads 0 where the executor
    produces them."""
    def alter(full):
        res = full.results.copy()
        res[len(res) * 3 // 4:] = 0
        return dataclasses.replace(full, results=res)
    _wrap_run_full(alter)


def lost_shards():
    """Only the first chip's rows of each chunk come back from the mesh:
    the other chips' results, hashes and leaf digests read 0."""
    from repro.core.executor import DEFAULT_CHUNK

    def alter(full):
        n = len(full.args)
        chunk = min(n, DEFAULT_CHUNK)
        lost = (full.args % chunk) >= chunk // 4
        out = {}
        for name in ("results", "hashes", "leaf_digests"):
            arr = getattr(full, name).copy()
            arr[lost] = 0
            out[name] = arr
        return dataclasses.replace(full, **out)
    _wrap_run_full(alter)


def one_chip_miner():
    """The miner is given a one-device mesh: every answer is right, and
    one chip does the work of four."""
    import bench.systems.jash_mesh as jm
    from repro.launch.mesh import make_mesh
    jm.make_host_mesh = lambda: make_mesh((1,), ("data",))


def replayed_block():
    """The executor hands back the first block it made of each size for
    every later jash: a program that reused an earlier block's results."""
    seen = {}

    def alter(full):
        return seen.setdefault(len(full.args), full)
    _wrap_run_full(alter)
