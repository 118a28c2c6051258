"""Faults planted beneath the timed path, for the tests that see
``correct`` come out false, and the probe semantics the check has to
hold under.  ``plant(name)`` patches the program in this process before
the benchmark runs."""
import numpy as np


def _probes(exact_key: bool):
    """``cmatrix.probe_edge`` and ``probe_vertex`` as they match an entry:
    on the fingerprint alone, or, with ``exact_key``, only where its
    stored chain index ``idx`` (``i * r + j``) names the chain position
    the query found it at, which tells the base row, and so the whole
    key, apart."""
    import jax
    import jax.numpy as jnp

    def probe_edge(nodes, node_mask, fs, fd, rows, cols, ts, te, *,
                   match_time):
        q, r = rows.shape
        wmask = jnp.where(node_mask, 1.0, 0.0)[:, None, None, None]
        pos = (jnp.arange(r, dtype=jnp.uint32)[:, None] * r
               + jnp.arange(r, dtype=jnp.uint32)[None, :])[None, :, :, None]

        def one(fs_i, fd_i, row_i, col_i, ts_i, te_i):
            at = (slice(None), row_i[:, None], col_i[None, :])
            match = (nodes.fp_s[at] == fs_i) & (nodes.fp_d[at] == fd_i)
            if exact_key:
                match &= nodes.idx[at] == pos
            if match_time:
                et = nodes.t[at]
                match &= (et >= ts_i) & (et <= te_i)
            return jnp.sum(jnp.where(match, nodes.w[at] * wmask, 0.0))

        ts = jnp.broadcast_to(jnp.asarray(ts, jnp.uint32), (q,))
        te = jnp.broadcast_to(jnp.asarray(te, jnp.uint32), (q,))
        return jax.vmap(one)(fs, fd, rows.astype(jnp.int32),
                             cols.astype(jnp.int32), ts, te)

    def probe_vertex(nodes, node_mask, fv, rows, ts, te, *, direction,
                     match_time):
        r = rows.shape[1]
        wmask = jnp.where(node_mask, 1.0, 0.0)[:, None, None, None]
        pos = jnp.arange(r, dtype=jnp.uint32)[None, :, None, None]

        def one(fv_i, row_i):
            if direction == "out":
                efp, ew, et, ei = (a[:, row_i, :, :] for a in (
                    nodes.fp_s, nodes.w, nodes.t, nodes.idx))
                at_pos = ei // r == pos
            else:
                efp, ew, et, ei = (a[:, :, row_i, :].transpose(0, 2, 1, 3)
                                   for a in (nodes.fp_d, nodes.w, nodes.t,
                                             nodes.idx))
                at_pos = ei % r == pos
            match = efp == fv_i
            if exact_key:
                match &= at_pos
            if match_time:
                match &= (et >= ts) & (et <= te)
            return jnp.sum(jnp.where(match, ew * wmask, 0.0))

        ts = jnp.asarray(ts, jnp.uint32)
        te = jnp.asarray(te, jnp.uint32)
        return jax.vmap(one)(fv, rows.astype(jnp.int32))

    return probe_edge, probe_vertex


PROBES = ("probes_fingerprint_only", "probes_exact_key")


def plant(name: str) -> None:
    from repro.core.higgs import HiggsSketch
    if name in PROBES:
        # the probes every query path traces, patched before the first
        from repro.core import cmatrix
        cmatrix.probe_edge, cmatrix.probe_vertex = _probes(
            name == "probes_exact_key")
    elif name == "state_unchanged":
        # an insert that returns with the state as it was
        HiggsSketch.insert = lambda self, src, dst, w, t: None
    elif name == "half_batch":
        # every other edge of each batch left out
        orig = HiggsSketch.insert

        def half(self, src, dst, w, t):
            keep = slice(0, None, 2)
            orig(self, src[keep], dst[keep], w[keep], t[keep])
        HiggsSketch.insert = half
    elif name == "answer_altered":
        # one edge answer off by one where the probes produce it
        import repro.api.planner as planner
        orig = planner.QueryPlanner._edge_batch

        def altered(self, *a, **kw):
            out = orig(self, *a, **kw)
            if len(out):
                out = np.array(out)
                out[0] += 1.0
            return out
        planner.QueryPlanner._edge_batch = altered
    else:
        raise ValueError(f"unknown fault {name!r}")
