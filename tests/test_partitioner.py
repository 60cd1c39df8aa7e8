"""Declarative partitioner (distributed/partitioner) + sharding-aware
checkpoints — the round-18 subsystem, on the 8-device virtual mesh.

The contract under test is the ISSUE acceptance line: ONE MeshConfig
shards the UNMODIFIED llama/gpt/bert `to_static` train step with loss
parity vs the hand-wired meta_parallel path, a clean D9-D11 audit, and a
data4×tp2 → data2×tp4 checkpoint restore that resumes bitwise.
"""
import json
import os
import shutil
import tempfile

import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis, ckpt
from paddle_tpu.distributed.partitioner import (
    MeshConfig, PartitionPlan, REPLICATED_RULES, infer_logical_axes,
    partition, restore_partitioned, save_partitioned, shard_model,
    spec_for_param)
from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

import faultinject as fi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V1_FIXTURE = os.path.join(REPO, "tests", "ckpt_fixtures", "ckpt_v1")


# ------------------------------------------------------------ helpers
def _tiny_llama_setup(mc=None, seed=0, **cfg_kw):
    """(model, opt, step): unmodified tiny LLaMA + AdamW train step,
    partitioned when a MeshConfig is given, plain to_static otherwise."""
    paddle.seed(seed)
    cfg = llama_tiny_config(**cfg_kw)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def step(ids, labels):
        loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    if mc is None:
        return model, opt, paddle.jit.to_static(step)
    return model, opt, partition(step, mc, model=model)


def _batches(n, seed=3, batch=8, seq=32, vocab=256):
    rs = np.random.RandomState(seed)
    return [(rs.randint(0, vocab, (batch, seq)).astype("int64"),
             rs.randint(0, vocab, (batch, seq)).astype("int64"))
            for _ in range(n)]


def _t(b):
    return paddle.to_tensor(b[0]), paddle.to_tensor(b[1])


def _drive(step, batches):
    return [float(step(*_t(b))) for b in batches]


def _state_np(model):
    return {k: v.numpy().copy() for k, v in model.state_dict().items()}


# ------------------------------------------------------------ MeshConfig
class TestMeshConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            MeshConfig(data=0)
        with pytest.raises(ValueError):
            MeshConfig(batch_axes=("nope",))
        with pytest.raises(ValueError):
            MeshConfig(stream_seq_axis="bogus")

    def test_shape_and_names(self):
        mc = MeshConfig(data=2, fsdp=2, tp=2)
        assert mc.axis_names == ("data", "fsdp", "tp")
        assert mc.num_devices == 8
        assert mc.describe() == "data2xfsdp2xtp2"
        # sep materializes only when > 1
        assert MeshConfig(sep=2).axis_names[-1] == "sep"

    def test_seq_axis_defaults(self):
        assert MeshConfig(tp=2).seq_axis == "tp"
        assert MeshConfig(sep=4).seq_axis == "sep"
        assert MeshConfig(tp=2, stream_seq_axis="data").seq_axis == "data"

    def test_build_mesh(self):
        mesh = MeshConfig(data=4, tp=2).build_mesh()
        assert dict(mesh.shape) == {"data": 4, "fsdp": 1, "tp": 2}

    def test_too_few_devices_raises(self):
        with pytest.raises(ValueError, match="needs 16 devices"):
            MeshConfig(data=16).build_mesh()

    def test_dict_roundtrip(self):
        mc = MeshConfig(data=2, tp=4)
        assert MeshConfig.from_dict(mc.to_dict()).axis_sizes == \
            mc.axis_sizes


# ------------------------------------------------------------ rule table
class TestRules:
    def test_spec_for_annotated_param(self):
        mc = MeshConfig(data=2, fsdp=2, tp=2)
        spec, notes = spec_for_param("w", (64, 64), ("embed", "heads"), mc)
        assert spec == ("fsdp", "tp") and not notes

    def test_divisibility_guard_drops_axis(self):
        mc = MeshConfig(tp=2)
        spec, notes = spec_for_param("w", (64, 63), ("embed", "heads"), mc)
        assert spec == (None, None)
        assert any("not divisible" in n for n in notes)

    def test_axis_reuse_guard(self):
        # both dims map to tp — the second dim must drop it (a
        # PartitionSpec may not repeat a mesh axis)
        mc = MeshConfig(tp=2)
        spec, notes = spec_for_param("w", (64, 64), ("heads", "heads"), mc)
        assert spec == ("tp", None)
        assert any("already used" in n for n in notes)

    def test_fsdp_min_size_guard(self):
        mc = MeshConfig(fsdp=2)
        spec, notes = spec_for_param("w", (8, 8), ("embed", "heads"), mc)
        assert spec == (None, None)
        assert any("fsdp_min_size" in n for n in notes)
        big, notes2 = spec_for_param("w", (64, 64), ("embed", "heads"), mc)
        assert big == ("fsdp", None) and not notes2

    def test_replicated_rules_shard_nothing(self):
        mc = MeshConfig(data=2, tp=2, rules=REPLICATED_RULES)
        spec, _ = spec_for_param("w", (64, 64), ("embed", "heads"), mc)
        assert spec == (None, None)

    def test_heuristics(self):
        mc = MeshConfig(tp=2)
        assert infer_logical_axes("wte.weight", (256, 64), mc) == \
            ("vocab", "embed")
        assert infer_logical_axes("fc.weight", (64, 128), mc) == \
            ("embed", "mlp")
        assert infer_logical_axes("fc.weight", (128, 64), mc) == \
            ("mlp", "embed")
        assert infer_logical_axes("q.weight", (64, 64), mc) == \
            ("embed", "heads")
        assert infer_logical_axes("b", (64,), mc) == ("norm",)
        assert infer_logical_axes("odd", (2, 3, 4), mc) is None


# ------------------------------------------------------------ placement
class TestShardModel:
    def test_params_placed_per_rules(self):
        mc = MeshConfig(data=2, fsdp=2, tp=2)
        model, _opt, _ = _tiny_llama_setup()
        plan = shard_model(model, mc)
        q = model.llama.layers[0].self_attn.q_proj.weight._data
        assert isinstance(q.sharding, NamedSharding)
        assert tuple(q.sharding.spec) == ("fsdp", "tp")
        emb = model.llama.embed_tokens.weight._data
        assert tuple(emb.sharding.spec) == ("tp", "fsdp")
        # annotated models guess nothing
        assert not plan.heuristic_params
        assert plan.summary()["sharded"] > 0

    def test_unannotated_model_heuristic_notes(self):
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(16, 64),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(64, 16))
        plan = shard_model(net, MeshConfig(data=4, tp=2))
        assert plan.heuristic_params           # every param was guessed
        notes = plan.to_findings()
        assert any(f.detector == "partitioner-heuristic" and
                   f.severity == "note" for f in notes)
        w = net[0].weight._data
        assert tuple(w.sharding.spec) == (None, "tp")   # (embed, mlp)


# ----------------------------------------------------- partitioned train
class TestPartitionTraining:
    def test_llama_parity_vs_hand_wired_meta_parallel(self):
        """THE acceptance criterion: one declarative config matches the
        fleet dp4×mp2 tensor+sequence-parallel path loss-for-loss on the
        unmodified model (weights synced — nn.Embedding and
        VocabParallelEmbedding draw different initializers)."""
        from paddle_tpu.distributed import fleet

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)

        paddle.seed(0)
        plain = LlamaForCausalLM(llama_tiny_config())
        paddle.seed(0)
        wired = LlamaForCausalLM(llama_tiny_config(
            tensor_parallel=True, sequence_parallel=True))
        wired.set_state_dict(_state_np(plain))
        wired_d = fleet.distributed_model(wired)

        o1 = paddle.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=plain.parameters())
        o2 = paddle.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=wired.parameters())

        def mkstep(m, o):
            def step(ids, labels):
                loss = m(ids, labels)
                loss.backward()
                o.step()
                o.clear_grad()
                return loss
            return step

        pstep = partition(mkstep(plain, o1), MeshConfig(data=4, tp=2),
                          model=plain)
        fstep = paddle.jit.to_static(mkstep(wired_d, o2))
        batches = _batches(4, seed=7)
        lp = _drive(pstep, batches)
        lf = _drive(fstep, batches)
        np.testing.assert_allclose(lp, lf, rtol=1e-6)

    @pytest.mark.parametrize("arch", ["gpt", "bert"])
    def test_gpt_bert_parity_vs_replicated(self, arch):
        """The same unmodified step, data2×tp2-partitioned vs entirely
        unpartitioned — sharding is placement, not math."""
        if arch == "gpt":
            from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

            def build():
                paddle.seed(0)
                m = GPTForCausalLM(GPTConfig(
                    vocab_size=128, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64))
                o = paddle.optimizer.AdamW(learning_rate=1e-3,
                                           parameters=m.parameters())

                def step(ids, labels):
                    loss = m(ids, labels)
                    loss.backward()
                    o.step()
                    o.clear_grad()
                    return loss
                return m, step

            batches = _batches(4, vocab=128)
        else:
            from paddle_tpu.text.models.bert import (
                BertConfig, BertForSequenceClassification)

            def build():
                paddle.seed(0)
                m = BertForSequenceClassification(BertConfig(
                    vocab_size=128, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=128,
                    max_position_embeddings=64, hidden_dropout_prob=0.0))
                o = paddle.optimizer.AdamW(learning_rate=1e-3,
                                           parameters=m.parameters())

                def step(ids, labels):
                    loss = m(ids, labels=labels)
                    loss.backward()
                    o.step()
                    o.clear_grad()
                    return loss
                return m, step

            rs = np.random.RandomState(3)
            batches = [(rs.randint(0, 128, (8, 16)).astype("int64"),
                        rs.randint(0, 2, (8,)).astype("int64"))
                       for _ in range(4)]

        m1, s1 = build()
        ref = _drive(paddle.jit.to_static(s1), batches)
        m2, s2 = build()
        part = _drive(partition(s2, MeshConfig(data=2, tp=2), model=m2),
                      batches)
        # sharded reductions group differently: ulp-level noise only
        np.testing.assert_allclose(part, ref, rtol=1e-5)

    def test_audit_clean_and_d9_coverage(self):
        """Clean D9-D11 at default flags on the partitioned train step —
        the mesh rides the CompiledFunction (_audit_mesh plumb-through),
        no re-declaration."""
        paddle.set_flags({"FLAGS_jit_debug_program": True})
        try:
            _model, _opt, step = _tiny_llama_setup(
                MeshConfig(data=2, fsdp=2, tp=2))
            for b in _batches(4):
                step(*_t(b))
            findings = analysis.audit_compiled(step, loc="part/step")
        finally:
            paddle.set_flags({"FLAGS_jit_debug_program": False})
        bad = [f for f in findings if f.severity != "note"]
        assert not bad, [f.message for f in bad]
        cov = [f for f in findings if f.detector == "spmd-coverage"
               and "coverage ok" in f.message]
        assert cov, "D9 did not confirm full mesh-axis stream coverage"

    def test_replicated_rules_fire_d9(self):
        """Fire fixture: a config whose rule table shards nothing must
        produce the D9 unsharded-stream warning — the detector gates the
        partitioner path too (silently-dead check)."""
        mc = MeshConfig(data=2, tp=2, rules=REPLICATED_RULES,
                        batch_axes=(), stream_seq_axis="data")
        paddle.set_flags({"FLAGS_jit_debug_program": True,
                          "FLAGS_partitioner_heuristics": False})
        try:
            _model, _opt, step = _tiny_llama_setup(mc)
            for b in _batches(4):
                step(*_t(b))
            findings = analysis.audit_compiled(step, loc="part/fire")
        finally:
            paddle.set_flags({"FLAGS_jit_debug_program": False,
                              "FLAGS_partitioner_heuristics": True})
        fired = [f for f in findings if f.detector == "spmd-coverage"
                 and f.severity == "warning"]
        assert fired, "D9 went silently dead on an all-replicated config"

    #: sep-free reference trajectory shared by both sep parametrizations
    #: (one full build+compile instead of two; batches are deterministic)
    _sep_ref: dict = {}

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_sep_axis_train_parity(self, impl):
        """sep-axis configs route attention through the existing
        ring/ulysses kernels; training numerics match the sep-free
        config at float tolerance (exact-attention kernels). For ring,
        D10's collective attribution is also the witness that the
        compiled program really contains the shard_map'd ppermute
        exchange, not dense attention."""
        batches = _batches(4)
        if "ref" not in self._sep_ref:
            _m1, _o1, ref_step = _tiny_llama_setup(MeshConfig(data=2))
            type(self)._sep_ref["ref"] = _drive(ref_step, batches)
        ref = self._sep_ref["ref"]
        debug = impl == "ring"
        paddle.set_flags({"FLAGS_partitioner_sep_impl": impl,
                          "FLAGS_jit_debug_program": debug})
        try:
            _m2, _o2, sep_step = _tiny_llama_setup(
                MeshConfig(data=2, sep=4))
            got = _drive(sep_step, batches)
            if debug:
                vol = analysis.jaxpr_collective_bytes(
                    sep_step.program_jaxpr())
                assert vol["per_axis"].get("sep", 0) > 0
                assert "ppermute" in vol["per_prim"]
        finally:
            paddle.set_flags({"FLAGS_partitioner_sep_impl": "ring",
                              "FLAGS_jit_debug_program": False})
        np.testing.assert_allclose(got, ref, rtol=1e-5)

    def test_partition_static_false_eager_debug_path(self):
        """static=False (the eager debugging escape) constrains the same
        flattened tensor leaves the compiled path does — kwarg tensors
        included — and trains finitely."""
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny_config())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())

        def step(ids, labels):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        estep = partition(step, MeshConfig(data=2, tp=2), model=model,
                          static=False)
        ids, labels = _t(_batches(1)[0])
        assert np.isfinite(float(estep(ids, labels)))
        # tensor passed as KWARG still gets its leaf constraint
        assert np.isfinite(float(estep(ids, labels=labels)))

    def test_config_too_big_for_host_raises(self):
        """A mesh that was asked for and cannot be built is an error:
        the step never runs unsharded under a sharded config's name."""
        with pytest.raises(ValueError, match="needs 16 devices"):
            _tiny_llama_setup(MeshConfig(data=16))


# --------------------------------------------- sharding-aware checkpoints
class TestShardedCheckpoint:
    def test_manifest_v2_records_mesh_and_spec(self):
        mc = MeshConfig(data=4, tp=2)
        model, opt, step = _tiny_llama_setup(mc)
        for b in _batches(3):
            step(*_t(b))
        root = tempfile.mkdtemp()
        try:
            res = save_partitioned(root, 3, model=model, optimizer=opt,
                                   config=mc)
            man = json.load(open(os.path.join(res["directory"],
                                              "manifest.json")))
            info = ckpt.manifest_shardings(man)
            assert info["version"] == 2
            assert info["leaves"], "no sharded leaves recorded"
            leaf = info["leaves"]["model/llama.embed_tokens.weight"]
            assert leaf["mesh"] == {"data": 4, "fsdp": 1, "tp": 2}
            assert leaf["spec"] == ["tp"]
            # per-shard files: strictly more shard files than leaves
            assert res["shards"] > len(man["tree"]["items"])
            ok, reason = ckpt.verify_checkpoint(res["directory"])
            assert ok, reason
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def test_reshard_on_restore_dp4tp2_to_dp2tp4(self):
        """dp4×tp2 → dp2×tp4: restored state bitwise, the resumed
        trajectory deterministic (two independent restores agree
        bitwise) and ulp-close to the uninterrupted source run."""
        mcA, mcB = MeshConfig(data=4, tp=2), MeshConfig(data=2, tp=4)
        batches = _batches(6)
        model, opt, step = _tiny_llama_setup(mcA)
        for b in batches[:3]:
            step(*_t(b))
        ref_state = _state_np(model)
        root = tempfile.mkdtemp()
        try:
            save_partitioned(root, 3, model=model, optimizer=opt,
                             config=mcA)
            cont_A = _drive(step, batches[3:])

            def resume_under_B():
                m, o, s = _tiny_llama_setup(mcB, seed=1)
                for b in batches[:3]:   # warm the compiled phases
                    s(*_t(b))
                r = restore_partitioned(root, model=m, optimizer=o,
                                        config=mcB)
                assert r.reason == "resharded" and r.step == 3
                assert r.saved_shardings   # v2 provenance present
                return m, _drive(s, batches[3:])

            _mB1, lB1 = resume_under_B()
            # state bitwise across the reshard (fresh restore, no steps)
            m2, o2, _s2 = _tiny_llama_setup(mcB, seed=1)
            r = restore_partitioned(root, model=m2, optimizer=o2,
                                    config=mcB)
            for k, v in _state_np(m2).items():
                np.testing.assert_array_equal(v, ref_state[k], err_msg=k)
            # placement really is the NEW config's
            q = m2.llama.layers[0].self_attn.q_proj.weight._data
            assert dict(q.sharding.mesh.shape)["tp"] == 4
            # determinism: a second independent restore+resume is bitwise
            _mB2, lB2 = resume_under_B()
            assert lB1 == lB2
            # and ulp-close to the uninterrupted dp4×tp2 continuation
            np.testing.assert_allclose(lB1, cont_A, rtol=1e-5)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def test_same_config_resume_is_bitwise(self):
        mc = MeshConfig(data=4, tp=2)
        batches = _batches(8)
        model, opt, step = _tiny_llama_setup(mc)
        for b in batches[:4]:
            step(*_t(b))
        root = tempfile.mkdtemp()
        try:
            save_partitioned(root, 4, model=model, optimizer=opt,
                             config=mc)
            uninterrupted = _drive(step, batches[4:])
            m2, o2, s2 = _tiny_llama_setup(mc, seed=1)
            for b in batches[:4]:
                s2(*_t(b))
            restore_partitioned(root, model=m2, optimizer=o2, config=mc)
            resumed = _drive(s2, batches[4:])
            assert resumed == uninterrupted   # bitwise
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def test_restore_onto_single_device(self):
        """dp4×tp2 → no config at all: restores replicated with the
        same bytes (the sharded manifest reassembles the global
        arrays)."""
        mc = MeshConfig(data=4, tp=2)
        model, opt, step = _tiny_llama_setup(mc)
        for b in _batches(3):
            step(*_t(b))
        ref_state = _state_np(model)
        root = tempfile.mkdtemp()
        try:
            save_partitioned(root, 3, model=model, optimizer=opt,
                             config=mc)
            m2, o2, s2 = _tiny_llama_setup(None, seed=1)
            r = restore_partitioned(root, model=m2, optimizer=o2)
            assert r.reason == "replicated"
            for k, v in _state_np(m2).items():
                np.testing.assert_array_equal(v, ref_state[k], err_msg=k)
            losses = _drive(s2, _batches(2, seed=11))
            assert all(np.isfinite(losses))
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def test_crash_mid_shard_write_restores_last_good(self):
        """Fault injection under the SHARDED layout: a crash after
        sub-shard K of the newer save leaves only debris; restore falls
        back to the older committed sharded checkpoint bit-exact."""
        mc = MeshConfig(data=4, tp=2)
        model, opt, step = _tiny_llama_setup(mc)
        for b in _batches(2):
            step(*_t(b))
        root = tempfile.mkdtemp()
        try:
            save_partitioned(root, 2, model=model, optimizer=opt,
                             config=mc)
            good = _state_np(model)
            step(*_t(_batches(3)[2]))
            with pytest.raises(fi.InjectedCrash):
                with fi.crash_after_shard(17):
                    save_partitioned(root, 3, model=model,
                                     optimizer=opt, config=mc)
            m2, o2, _ = _tiny_llama_setup(mc, seed=1)
            r = restore_partitioned(root, model=m2, optimizer=o2,
                                    config=mc)
            assert r.step == 2
            for k, v in _state_np(m2).items():
                np.testing.assert_array_equal(v, good[k], err_msg=k)
            # the torn temp dir is debris, not a candidate
            assert ckpt.clean_debris(root)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def test_v1_fixture_restores_as_replicated_with_reason(self):
        """Backward-compat pin against the COMMITTED v1 fixture: the v2
        reader restores it, manifest_shardings reports version 1 with no
        sharded leaves, and restore_partitioned names the reason."""
        r = ckpt.restore_checkpoint(V1_FIXTURE)
        assert r.step == 7
        np.testing.assert_array_equal(
            r.tree["model"]["w"],
            np.arange(12, dtype=np.float32).reshape(3, 4))
        np.testing.assert_array_equal(
            r.tree["model"]["b"], np.array([0.5, -1.5, 2.0], np.float32))
        info = ckpt.manifest_shardings(r.manifest)
        assert info["version"] == 1 and not info["leaves"]
        pr = restore_partitioned(V1_FIXTURE)
        assert pr.reason == "manifest_v1_replicated"
        assert pr.step == 7 and not pr.saved_shardings

    def test_v2_roundtrip_through_plain_restore(self):
        """A sharded save is a NORMAL checkpoint: plain
        ckpt.restore_checkpoint reassembles every leaf to the exact
        global bytes (one code path for partitioned and not)."""
        mc = MeshConfig(data=2, fsdp=2, tp=2)
        model, _opt, _step = _tiny_llama_setup(mc)
        shard_model(model, mc)
        tree = {"model": dict(model.state_dict())}
        ref = _state_np(model)
        root = tempfile.mkdtemp()
        try:
            ckpt.save_checkpoint(root, 1, tree, sharded=True)
            r = ckpt.restore_checkpoint(root)
            for k, v in r.tree["model"].items():
                np.testing.assert_array_equal(np.asarray(v), ref[k],
                                              err_msg=k)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def test_async_saver_sharded(self):
        """AsyncCheckpointer(sharded=True) commits per-shard in the
        background — the round-12 machinery carries the v2 layout."""
        mc = MeshConfig(data=4, tp=2)
        model, _opt, _step = _tiny_llama_setup(mc)
        shard_model(model, mc)
        root = tempfile.mkdtemp()
        try:
            saver = ckpt.AsyncCheckpointer(root, sharded=True)
            saver.save(1, {"model": dict(model.state_dict())})
            saver.wait()
            saver.close()
            r = ckpt.restore_checkpoint(root)
            info = ckpt.manifest_shardings(r.manifest)
            assert info["version"] == 2 and info["leaves"]
        finally:
            shutil.rmtree(root, ignore_errors=True)


# ------------------------------------------------------------ hapi mesh
class TestHapiMesh:
    def test_prepare_with_mesh_places_and_fits(self):
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(16, 64),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(64, 8))
        m = paddle.hapi.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=net.parameters())
        m.prepare(opt, paddle.nn.MSELoss(), mesh=MeshConfig(data=4, tp=2))
        assert isinstance(m._mesh_plan, PartitionPlan)
        w = net[0].weight._data
        assert "tp" in str(w.sharding.spec)
        rs = np.random.RandomState(0)
        data = [(rs.randn(16).astype("float32"),
                 rs.randn(8).astype("float32")) for _ in range(16)]
        m.fit(data, batch_size=8, epochs=1, verbose=0)

    def test_fit_mesh_kwarg(self):
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 32),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(32, 4))
        m = paddle.hapi.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=net.parameters())
        m.prepare(opt, paddle.nn.MSELoss())
        rs = np.random.RandomState(0)
        data = [(rs.randn(8).astype("float32"),
                 rs.randn(4).astype("float32")) for _ in range(8)]
        m.fit(data, batch_size=8, epochs=1, verbose=0,
              mesh=MeshConfig(data=8))
        assert m._mesh_config is not None

    def test_mesh_type_error(self):
        m = paddle.hapi.Model(paddle.nn.Linear(4, 4))
        with pytest.raises(TypeError):
            m.prepare(mesh={"data": 4})

    def test_mesh_too_big_for_host_raises(self):
        m = paddle.hapi.Model(paddle.nn.Linear(4, 4))
        with pytest.raises(ValueError, match="needs 64 devices"):
            m.prepare(mesh=MeshConfig(data=64))


def test_partitioner_in_quick_tier():
    """This module must stay in the `pytest -m quick` tier."""
    from conftest import QUICK_MODULES

    assert "test_partitioner.py" in QUICK_MODULES
