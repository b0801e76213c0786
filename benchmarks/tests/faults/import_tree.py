"""Faults planted under the timed path of the ``import_tree`` driver
(``Fleet.merge_tree_payloads``): ``correct`` must come out false under
each.  One chip and no carried state: no exchange between chips, no step
that returns its state unchanged."""

FAULTS = ["answer_altered", "half_of_the_batch_left_out",
          "fallback_counter_moved", "refusals_applied"]


def plant(monkeypatch, fault: str) -> None:
    from loro_tpu.obs import metrics as obs
    from loro_tpu.ops import tree_batch
    from loro_tpu.parallel.fleet import Fleet

    if fault == "refusals_applied":
        # the replay's cycle rule ignored: a walk of no step finds no cycle
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=(1,))
        def no_cycle_rule(rows, n_nodes):
            parents, _eff, stats = tree_batch.tree_replay(rows, n_nodes, 0, False)
            alive = jnp.where(tree_batch.is_deleted_batch(parents),
                              tree_batch.TRASH, parents)
            return jnp.concatenate([alive, stats[:, :2]], axis=1)

        monkeypatch.setattr(tree_batch, "tree_import_batch",
                            lambda rows, n_nodes, _want_eff: no_cycle_rule(rows, n_nodes))
        return
    real = Fleet.merge_tree_payloads
    calls = {"n": 0}

    def broken(self, payloads, cid):
        calls["n"] += 1
        if fault == "half_of_the_batch_left_out":
            return real(self, payloads[: len(payloads) // 2], cid)
        out = real(self, payloads, cid)
        if calls["n"] < 2:  # the warm-up call stays sound
            return out
        if fault == "answer_altered":
            node, parent = next((k, v) for k, v in out[-1].items() if v is not None)
            out[-1][node] = None
        elif fault == "fallback_counter_moved":
            obs.counter("fleet.host_fallback_total").inc(where="test")
        return out

    monkeypatch.setattr(Fleet, "merge_tree_payloads", broken)
