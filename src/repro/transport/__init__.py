"""Real two-party deployment: each party is its own OS process.

``SocketComm`` implements the ``Comm`` interface over one TCP connection
(local party dimension 1 — the per-process layout the mesh backend
already proved the protocol against), with a handshake that refuses
mismatched sessions/plans, typed timeout/crash failures the PR-6
resilience stack heals, payload-exact byte accounting against
``core.schedule``, and optional link shaping (injected RTT + bandwidth
cap) so LAN/WAN latency predictions are falsifiable against measured
wall-clock.

Compose via ``api.Session.connect`` (socket -> ResilientComm ->
JournaledComm), run a party process with ``python -m
repro.launch.party_host``, and serve requests through
``repro.serve.Frontend`` + ``EngineLink`` (leader) against a
``serve_follower`` loop (follower).  See ``docs/deployment.md``.
"""
from .socket import (HEADER, LinkShaper, SocketComm, free_port,
                     parse_address)
from .job import load_job, load_party, pool_treedef, resolve_config, \
    smoke_job_inputs, write_job, write_smoke_job
from .engine_link import EngineLink, serve_follower, tenant_provider_factory

__all__ = [
    "HEADER", "LinkShaper", "SocketComm", "free_port", "parse_address",
    "load_job", "load_party", "pool_treedef", "resolve_config",
    "smoke_job_inputs", "write_job", "write_smoke_job", "EngineLink", "serve_follower", "tenant_provider_factory",
]
