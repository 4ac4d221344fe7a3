"""The store under test, opened and served as a configuration says."""

from __future__ import annotations

#: Tenant the benchmark's clients save and load under.
TENANT = "bench"


def open_store(config: dict, path: str):
    """``NeurStore`` at ``path`` with the configuration's store settings."""
    from repro.store import NeurStore

    s = config["store"]
    return NeurStore.open(path, tau=s["tau"], tolerance=s["tolerance"],
                          pool_bytes=s["pool_bytes"])


def serve(config: dict, store):
    """``(server, client)``: the HTTP front door on ``store``, started,
    and one client of it."""
    from repro.server import ModelStoreServer, StoreClient

    server = ModelStoreServer(
        store.engine, port=0,
        response_cache_bytes=config["store"]["response_cache_bytes"]).start()
    client = StoreClient(server.host, server.port, tenant=TENANT,
                         timeout=1200.0)
    return server, client


def save_request(config: dict, name: str, tensors: dict):
    from repro.store import SaveRequest

    return SaveRequest(name, tensors, architecture={"config": config["name"]})


def catalog_name(name: str) -> str:
    """The name a client's model has in the embedded store."""
    from repro.server.quota import tenant_model_name

    return tenant_model_name(TENANT, name)
