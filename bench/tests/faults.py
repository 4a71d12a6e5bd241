"""Faults planted in the program underneath a run: each breaks the timed
path as a wrong optimisation could, and the check has to read it."""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _tree_msgs(gl_new, gl_old):
    return jax.tree_util.tree_map(lambda a, b: a - b, gl_new, gl_old)


def plant_train(monkeypatch, fault: str) -> None:
    from repro.methods import substrates
    from repro.optim import distributed

    if fault == "unchanged":
        make = distributed.make_method

        def frozen(*a, **kw):
            method = make(*a, **kw)
            return method._replace(step=lambda s, d=None: s)
        monkeypatch.setattr(distributed, "make_method", frozen)
        return

    orig = substrates.TreeCompression.estimator_update

    def broken(self, key, h_new, h, g_local, a, aux=None):
        agg, h_out, gl, pay = orig(self, key, h_new, h, g_local, a, aux)
        m = _tree_msgs(gl, g_local)
        if fault == "half_batch":
            # half of the nodes left out, the mean taken over the rest
            agg = jax.tree_util.tree_map(
                lambda x: jnp.mean(x[: x.shape[0] // 2].astype(F32), 0), m)
        elif fault == "altered":
            # node 0's message negated where it is produced
            m = jax.tree_util.tree_map(lambda x: x.at[0].multiply(-1), m)
            gl = jax.tree_util.tree_map(jnp.add, g_local, m)
            agg = jax.tree_util.tree_map(
                lambda x: jnp.mean(x.astype(F32), 0), m)
        else:
            raise ValueError(fault)
        return agg, h_out, gl, pay
    monkeypatch.setattr(substrates.TreeCompression, "estimator_update",
                        broken)
