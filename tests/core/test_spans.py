"""The program's own spans (``repro.obs``) in the streaming trainer and the
serve queue: one tree per trainer call with a ``fit.chunk`` per chunk, and
per serve request three spans that tile its life in the queue exactly."""
import jax
import numpy as np

from repro import obs
from repro.core import (AsyncBatchQueue, MulticlassSVMConfig, export_model,
                        fit_multiclass, fit_multiclass_stream)
from repro.core.multiclass import train_chunk_multiclass
from repro.data import ArrayChunks, make_blobs_multiclass

N_CLASSES, DIM = 3, 6
CFG = MulticlassSVMConfig.create(N_CLASSES, budget=16, lambda_=1e-3,
                                 gamma=0.5, batch_size=4)


def _data(n, seed=0):
    x, y = make_blobs_multiclass(jax.random.PRNGKey(seed), n, DIM,
                                 n_classes=N_CLASSES)
    return np.asarray(x, np.float32), np.asarray(y)


def _newest_root(name):
    r = obs.RING.records()
    return int(r["root_id"][r["name"] == name].max())


def test_stream_spans_per_chunk(tmp_path, watchdog):
    watchdog(300)
    x, y = _data(144)
    fit_multiclass_stream(CFG, ArrayChunks(x, y, 48), epochs=2, seed=1,
                          prefetch=1, ckpt_every=1,
                          ckpt_dir=str(tmp_path / "ck"))
    root = _newest_root("fit.stream")
    r = obs.RING.records(root)
    name, sid, par = r["name"], r["span_id"], r["parent_id"]
    assert list(name).count("fit.stream") == 1
    chunks = np.flatnonzero(name == "fit.chunk")
    assert len(chunks) == 6                       # 3 chunks x 2 epochs
    order = np.argsort(r["start_ns"][chunks])
    assert [(r["epoch"][i], r["pos"][i]) for i in chunks[order]] == [
        (e, p) for e in range(2) for p in range(3)]
    assert set(r["rows"][chunks]) == {48}
    for i in chunks:
        kids = list(name[par == sid[i]])
        assert kids.count("stream.wait") == 1
        assert kids.count("chunk.launch") == 1
        assert kids.count("ckpt.save") == 1
        save = sid[(par == sid[i]) & (name == "ckpt.save")][0]
        assert sorted(name[par == save]) == ["ckpt.copy", "ckpt.sync",
                                             "ckpt.write"]
        copy = (par == save) & (name == "ckpt.copy")
        write = (par == save) & (name == "ckpt.write")
        assert r["bytes"][copy][0] == r["bytes"][write][0] > 0
    # the prefetch worker's loads and transfers hang under the same root
    worker = r["thread"] == "chunk-stager"
    assert np.sum(worker & (name == "stream.load")) == 6
    assert np.sum(worker & (name == "stream.stage")) == 6
    assert set(r["thread"][name == "fit.chunk"]) == {"MainThread"}


def test_chunk_program_name_keeps_train_chunk():
    """The benchmark finds chunk programs in a trace by this substring."""
    from repro.core.multiclass import init_multiclass_state
    x, y = _data(16)
    state = init_multiclass_state(CFG, DIM)
    hlo = train_chunk_multiclass.lower(
        CFG, CFG.table(), state, x.reshape(4, 4, DIM),
        y.reshape(4, 4)).compile().as_text()
    assert hlo.splitlines()[0].split()[1].startswith(
        "jit_train_chunk_multiclass")


def _model():
    x, y = _data(96, seed=2)
    return export_model(fit_multiclass(CFG, x, y, epochs=1, seed=0), 0.5), x


def test_serve_spans_tile_each_request(watchdog):
    watchdog(300)
    model, x = _model()
    q = AsyncBatchQueue(model, max_batch=16, min_bucket=4)
    try:
        root = q._root.root_id
        q.warmup()
        assert obs.RING.records(root)["name"].size == 0   # warmup: none
        sizes = [3, 40, 0, 16, 1]                    # 40 takes 3 launches
        spans = {}
        for s in sizes:
            t_before = obs.now_ns()
            ticket = q.submit(x[:s])
            q.take(ticket, timeout=60)
            spans[ticket] = (s, t_before, obs.now_ns())
    finally:
        q.close()
    r = obs.RING.records(root)
    name = r["name"]
    launches = np.sort(r["start_ns"][name == "serve.launch"])
    assert len(launches) == 1 + 3 + 1 + 1
    for ticket, (s, t_before, t_after) in spans.items():
        mine = r["ticket"] == ticket
        assert sorted(name[mine]) == ["serve.handoff", "serve.inflight",
                                      "serve.wait"]
        got = {n: (r["start_ns"][mine & (name == n)][0],
                   r["end_ns"][mine & (name == n)][0])
               for n in ("serve.wait", "serve.inflight", "serve.handoff")}
        wait, inflight, handoff = (got["serve.wait"], got["serve.inflight"],
                                   got["serve.handoff"])
        assert wait[1] == inflight[0] and inflight[1] == handoff[0]
        assert t_before <= wait[0] <= wait[1] <= handoff[1] <= t_after
        if s:
            # the launch carrying the request's last rows ends its wait
            assert wait[1] in launches
            n_launches = np.sum((launches >= wait[0]) & (launches <= wait[1]))
            assert n_launches == -(-s // 16)
    assert set(r["thread"][name == "serve.launch"]) == {"serve-dispatch"}
    assert set(r["bucket"][name == "serve.assemble"]) <= set(q.buckets)
