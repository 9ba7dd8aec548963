"""Serving runtime + ALS endpoint tests over real HTTP (mirrors reference
AbstractServingTest/RecommendTest/IngestTest/PreferenceTest/ReadOnlyTest etc.,
SURVEY §4.3 — there JerseyTest+Grizzly, here the real aiohttp layer on a free
port with a model published through the update topic)."""

import gzip
import json
import time

import httpx
import numpy as np
import pytest

from oryx_tpu.common import config as cfg
from oryx_tpu.common import ioutils
from oryx_tpu.models.als import data as d
from oryx_tpu.models.als import pmml_codec
from oryx_tpu.models.als import train as tr
from oryx_tpu.pmml import pmmlutils
from oryx_tpu.serving.app import ServingLayer
from oryx_tpu.transport import topic as tp


def _train_tiny(tmp_path):
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((25, 3)) @ rng.standard_normal((3, 15))
    lines = []
    for u in range(25):
        for i in np.argsort(-scores[u])[:5]:
            lines.append(f"u{u},i{i},1,{u * 100 + int(i)}")
    batch = d.prepare(lines, implicit=True)
    x, y = tr.als_train(batch, features=4, lam=0.001, alpha=1.0, implicit=True,
                        iterations=3, chunk=256)
    pmml = pmml_codec.model_to_pmml(
        np.asarray(x), np.asarray(y), batch.users.index_to_id, batch.items.index_to_id,
        4, 0.001, 1.0, True, False, 1e-5, tmp_path,
    )
    known = {}
    for it in d.parse_lines(lines):
        known.setdefault(it.user, []).append(it.item)
    return pmml, batch, known


def _publish_to_topic(pmml, tmp_path, known, broker_url="memory:"):
    prod = tp.TopicProducerImpl(broker_url, "OryxUpdate")
    prod.send("MODEL", pmmlutils.to_string(pmml))
    for id_, vec in pmml_codec.read_features(tmp_path / "Y"):
        prod.send("UP", json.dumps(["Y", id_, [float(v) for v in vec]]))
    for id_, vec in pmml_codec.read_features(tmp_path / "X"):
        prod.send("UP", json.dumps(["X", id_, [float(v) for v in vec], known.get(id_, [])]))


@pytest.fixture(scope="module")
def serving(tmp_path_factory):
    tp.reset_memory_brokers()
    tmp_path = tmp_path_factory.mktemp("als-model")
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        },
        cfg.get_default(),
    )
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    pmml, batch, known = _train_tiny(tmp_path)
    _publish_to_topic(pmml, tmp_path, known)
    layer = ServingLayer(config)
    layer.start()
    base = f"http://127.0.0.1:{port}"
    client = httpx.Client(base_url=base, timeout=30)
    # wait for readiness
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if client.get("/ready").status_code == 200:
            break
        time.sleep(0.1)
    else:
        pytest.fail("serving layer never became ready")
    yield client, layer, batch, known
    client.close()
    layer.close()
    tp.reset_memory_brokers()


def test_ready_and_unknown_route(serving):
    client = serving[0]
    assert client.get("/ready").status_code == 200
    assert client.get("/nope").status_code == 404


def test_recommend_json_and_csv(serving):
    client, _, batch, known = serving
    user = batch.users.index_to_id[0]
    r = client.get(f"/recommend/{user}")
    assert r.status_code == 200
    recs = r.json()
    assert len(recs) == 10 and {"id", "value"} <= set(recs[0])
    # known items excluded by default
    assert set(known[user]).isdisjoint({x["id"] for x in recs})
    # considerKnownItems=true allows them back
    r2 = client.get(f"/recommend/{user}?considerKnownItems=true&howMany=15")
    ids2 = {x["id"] for x in r2.json()}
    assert set(known[user]) & ids2
    # CSV rendering
    r3 = client.get(f"/recommend/{user}", headers={"Accept": "text/csv"})
    assert r3.status_code == 200
    first = r3.text.splitlines()[0].split(",")
    assert len(first) == 2 and float(first[1])


def test_recommend_params_and_errors(serving):
    client, _, batch, _ = serving
    user = batch.users.index_to_id[0]
    top2 = client.get(f"/recommend/{user}?howMany=2").json()
    paged = client.get(f"/recommend/{user}?howMany=1&offset=1").json()
    assert paged[0]["id"] == top2[1]["id"]
    assert client.get(f"/recommend/{user}?howMany=0").status_code == 400
    assert client.get("/recommend/no-such-user").status_code == 404


def test_recommend_to_many_and_anonymous(serving):
    client, _, batch, _ = serving
    u0, u1 = batch.users.index_to_id[:2]
    r = client.get(f"/recommendToMany/{u0}/{u1}")
    # both users' known items excluded; tiny catalog may not fill howMany
    assert r.status_code == 200 and 0 < len(r.json()) <= 10
    i0, i1 = batch.items.index_to_id[:2]
    r2 = client.get(f"/recommendToAnonymous/{i0}=2/{i1}")
    assert r2.status_code == 200
    ids = {x["id"] for x in r2.json()}
    assert i0 not in ids and i1 not in ids  # context items excluded
    r3 = client.get(f"/recommendWithContext/{u0}/{i0}")
    assert r3.status_code == 200


def test_similarity_and_estimates(serving):
    client, _, batch, _ = serving
    i0, i1 = batch.items.index_to_id[:2]
    u0 = batch.users.index_to_id[0]
    sim = client.get(f"/similarity/{i0}/{i1}")
    assert sim.status_code == 200 and len(sim.json()) > 0
    s2i = client.get(f"/similarityToItem/{i0}/{i1}").json()
    assert len(s2i) == 1 and -1.001 <= s2i[0]["value"] <= 1.001
    est = client.get(f"/estimate/{u0}/{i0}/{i1}").json()
    assert len(est) == 2
    efa = client.get(f"/estimateForAnonymous/{i0}/{i1}=1.5")
    assert efa.status_code == 200
    assert isinstance(efa.json(), float)


def test_because_surprising_known_popular(serving):
    client, _, batch, known = serving
    u0 = batch.users.index_to_id[0]
    some_item = known[u0][0]
    because = client.get(f"/because/{u0}/{some_item}").json()
    assert because and because[0]["id"] in known[u0]
    surprising = client.get(f"/mostSurprising/{u0}").json()
    assert surprising and surprising[0]["id"] in known[u0]
    ki = client.get(f"/knownItems/{u0}").json()
    assert sorted(known[u0]) == ki
    pop = client.get("/mostPopularItems").json()
    assert pop and pop[0]["count"] >= pop[-1]["count"]
    active = client.get("/mostActiveUsers?howMany=3").json()
    assert len(active) == 3
    rep = client.get("/popularRepresentativeItems").json()
    assert len(rep) == 4  # one per feature


def test_all_ids(serving):
    client, _, batch, _ = serving
    users = client.get("/user/allIDs").json()
    items = client.get("/item/allIDs").json()
    assert set(users) == set(batch.users.index_to_id)
    assert set(items) == set(batch.items.index_to_id)


def test_pref_and_ingest_write_input_topic(serving):
    client = serving[0]
    broker = tp.get_broker("memory:")
    before = broker.size("OryxInput")
    assert client.post("/pref/uX/iY", content="3.0").status_code == 200
    assert client.delete("/pref/uX/iY").status_code == 200
    msgs = broker.read("OryxInput", before)
    assert len(msgs) == 2
    assert msgs[0].message.startswith("uX,iY,3.0,")
    assert msgs[1].message.startswith("uX,iY,,")
    assert client.post("/pref/uX/iY", content="junk").status_code == 400
    # bulk ingest incl. gzip
    before = broker.size("OryxInput")
    assert client.post("/ingest", content="a,b,1\nc,d,2\n").status_code == 200
    gz = gzip.compress(b"e,f,3\n")
    assert client.post(
        "/ingest", content=gz, headers={"Content-Encoding": "gzip"}
    ).status_code == 200
    msgs = broker.read("OryxInput", before)
    assert [m.message for m in msgs] == ["a,b,1", "c,d,2", "e,f,3"]


def test_503_before_model_loaded(tmp_path):
    tp.reset_memory_brokers()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        },
        cfg.get_default(),
    )
    layer = ServingLayer(config)
    layer.start()
    try:
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=10) as c:
            assert c.get("/ready").status_code == 503
            assert c.get("/recommend/u1").status_code == 503
    finally:
        layer.close()
        tp.reset_memory_brokers()


def test_read_only_and_auth(tmp_path):
    tp.reset_memory_brokers()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.api.read-only": True,
            "oryx.serving.api.user-name": "oryx",
            "oryx.serving.api.password": "pass",
            "oryx.serving.api.auth-scheme": "basic",
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        },
        cfg.get_default(),
    )
    layer = ServingLayer(config)
    layer.start()
    try:
        base = f"http://127.0.0.1:{port}"
        with httpx.Client(base_url=base, timeout=10) as c:
            assert c.post("/ingest", content="a,b,1").status_code == 401  # no auth
        with httpx.Client(base_url=base, timeout=10, auth=("oryx", "pass")) as c:
            assert c.post("/ingest", content="a,b,1").status_code == 403  # read-only
    finally:
        layer.close()
        tp.reset_memory_brokers()


def test_digest_auth(tmp_path):
    """RFC 7616 digest challenge/response — the default scheme, for wire
    parity with the reference's DIGEST InMemoryRealm
    (ServingLayer.java:293-321)."""
    tp.reset_memory_brokers()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.api.user-name": "oryx",
            "oryx.serving.api.password": "pass",
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources": "oryx_tpu.serving.resources.als",
        },
        cfg.get_default(),
    )
    layer = ServingLayer(config)
    layer.start()
    try:
        base = f"http://127.0.0.1:{port}"
        with httpx.Client(base_url=base, timeout=10) as c:
            r = c.get("/ready")
            assert r.status_code == 401  # no credentials
            challenges = r.headers.get_list("WWW-Authenticate")
            assert any(ch.startswith("Digest ") for ch in challenges)
            assert any('qop="auth"' in ch for ch in challenges)
            # basic credentials must NOT satisfy a digest realm
            assert c.get("/ready", auth=("oryx", "pass")).status_code == 401
        # httpx's DigestAuth implements the client side of the handshake
        with httpx.Client(
            base_url=base, timeout=10, auth=httpx.DigestAuth("oryx", "pass")
        ) as c:
            assert c.get("/ready").status_code in (200, 503)  # authed through
        with httpx.Client(
            base_url=base, timeout=10, auth=httpx.DigestAuth("oryx", "WRONG")
        ) as c:
            assert c.get("/ready").status_code == 401
    finally:
        layer.close()
        tp.reset_memory_brokers()


def test_tls_serving(tmp_path):
    """HTTPS via keystore-file/key-alias config (SecureAPIConfigIT equivalent)."""
    import subprocess

    cert = tmp_path / "cert.pem"
    key = tmp_path / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )
    tp.reset_memory_brokers()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            # TLS binds secure-port (ServingLayer connector split)
            "oryx.serving.api.secure-port": port,
            "oryx.serving.api.keystore-file": str(cert),
            "oryx.serving.api.key-alias": str(key),
            "oryx.serving.model-manager-class":
                "oryx_tpu.example.wordcount.ExampleServingModelManager",
            "oryx.serving.application-resources": "oryx_tpu.example.resources",
        },
        cfg.get_default(),
    )
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    tp.TopicProducerImpl("memory:", "OryxUpdate").send("MODEL", "{\"a\": 1}")
    layer = ServingLayer(config)
    layer.start()
    try:
        with httpx.Client(base_url=f"https://127.0.0.1:{port}", verify=False,
                          timeout=30) as client:
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if client.get("/ready").status_code == 200:
                    break
                time.sleep(0.2)
            else:
                pytest.fail("TLS serving never ready")
            assert client.get("/distinct").json() == {"a": 1}
    finally:
        layer.close()
        tp.reset_memory_brokers()


def test_precompile_batches_warms_pow2_ladder(tmp_path, monkeypatch):
    """With precompile-batches on, a ready model's batched top-N programs
    are exercised in the background at pow2 sizes (smallest first, so the
    replica turns ready incrementally) and a MODEL handoff's first client
    burst pays no XLA compiles."""
    from oryx_tpu.models.als.serving import ALSServingModel

    sizes = []
    orig = ALSServingModel.top_n_batch

    def recording(self, qs, how_many, alloweds=None, excluded=None):
        sizes.append(len(qs))
        return orig(self, qs, how_many, alloweds, excluded)

    monkeypatch.setattr(ALSServingModel, "top_n_batch", recording)

    tp.reset_memory_brokers()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources":
                "oryx_tpu.serving.resources.als",
            "oryx.serving.compute.precompile-batches": True,
            "oryx.serving.compute.coalesce-max-batch": 16,
        },
        cfg.get_default(),
    )
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    pmml, batch, known = _train_tiny(tmp_path)
    _publish_to_topic(pmml, tmp_path, known)
    layer = ServingLayer(config)
    layer.start()
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if layer._warmer is not None and layer._warmer.warmed_models:
                break
            time.sleep(0.1)
        else:
            pytest.fail("warmer never warmed a model")
        # each bucket executes THREE times — exclusion-free, then once at
        # each over-fetched width the default /recommend path's known items
        # can ask for (topn._OVERFETCH_ROOM) — smallest bucket first so the
        # replica turns ready incrementally
        assert sizes[:12] == [1, 1, 1, 2, 2, 2, 4, 4, 4, 8, 8, 8], sizes
        # the completed ladder marked the shared warmup state warm-ready
        from oryx_tpu.common import compilecache

        assert compilecache.warmup_state().ready(1.0)
        assert compilecache.warmup_state().snapshot() == {"done": 5, "total": 5}
    finally:
        layer.close()
        tp.reset_memory_brokers()
