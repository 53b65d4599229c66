"""The run manifest: ``--metrics-out run.json``.

One JSON document per invocation tying together what three artifacts
used to carry separately: the environment it ran in, the backend it
actually dispatched to (obs/provenance.py), the span totals of where
the wall clock went, and the full metrics-registry snapshot. A run
whose manifest says ``"platform": "cpu"`` can never be mistaken for
device evidence.
"""

from __future__ import annotations

import datetime
import json
import os

from .metrics import MetricsRegistry, get_registry
from .provenance import backend_provenance, env_provenance
from .tracing import Tracer, get_tracer

#: keys every manifest must carry — validated by :func:`load_manifest`
#: (a manifest missing one of these is not run evidence)
REQUIRED_KEYS = ("schema", "ts", "argv", "env", "backend", "spans",
                 "metrics", "trace_id")

#: current writer version. Minor bumps (1.x) ADD fields and must stay
#: readable by every 1.* consumer (readers meet manifests from many
#: versions); a major bump means the REQUIRED_KEYS contract
#: itself changed and old readers must refuse loudly.
SCHEMA_PREFIX = "goleft-tpu.run-manifest/"
SCHEMA_MAJOR = 1
SCHEMA = f"{SCHEMA_PREFIX}1.3"

#: subsystem-contributed manifest sections (1.2): name -> provider().
#: A provider returning None omits its section; a raising provider
#: degrades to an error stub — manifest writing must never fail the
#: run it is documenting. The resilience subsystem registers its
#: quarantine/checkpoint block here.
_SECTIONS: dict = {}


def register_section(name: str, provider) -> None:
    if name in REQUIRED_KEYS:
        raise ValueError(f"cannot shadow required manifest key {name!r}")
    _SECTIONS[name] = provider


def parse_schema_version(schema: str) -> tuple[int, int]:
    """``goleft-tpu.run-manifest/1.2`` -> (1, 2); a bare ``/1`` means
    (1, 0). Raises ValueError on anything else."""
    if not isinstance(schema, str) \
            or not schema.startswith(SCHEMA_PREFIX):
        raise ValueError(f"not a run-manifest schema id: {schema!r}")
    ver = schema[len(SCHEMA_PREFIX):]
    major, _, minor = ver.partition(".")
    try:
        return int(major), int(minor) if minor else 0
    except ValueError:
        raise ValueError(
            f"unparseable run-manifest version: {schema!r}") from None


def build_manifest(tracer: Tracer | None = None,
                   registry: MetricsRegistry | None = None,
                   trace_id: str | None = None,
                   argv: list[str] | None = None,
                   extra: dict | None = None) -> dict:
    tracer = tracer if tracer is not None else get_tracer()
    registry = registry if registry is not None else get_registry()
    doc = {
        "schema": SCHEMA,
        "ts": datetime.datetime.now(datetime.timezone.utc)
                .isoformat(timespec="seconds"),
        "argv": list(argv) if argv is not None else None,
        "env": env_provenance(),
        "backend": backend_provenance(),
        "spans": tracer.summary(trace_id=trace_id),
        # the span summary is only as complete as the ring: the drop
        # count (and a plain truncation flag, added in 1.1) ride next
        # to it so a partial summary is self-describing
        "spans_dropped": tracer.spans_dropped,
        "spans_truncated": tracer.spans_dropped > 0,
        "metrics": registry.snapshot(),
        "trace_id": trace_id,
    }
    for name in sorted(_SECTIONS):
        try:
            section = _SECTIONS[name]()
        except Exception as e:  # noqa: BLE001 — never fail the run
            section = {"error": repr(e)}
        if section is not None:
            doc[name] = section
    if extra:
        doc.update(extra)
    return doc


def write_manifest(path: str, **kw) -> dict:
    doc = build_manifest(**kw)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=False)
        fh.write("\n")
    os.replace(tmp, path)
    return doc


def load_manifest(path: str) -> dict:
    """Parse + validate a manifest: the REQUIRED_KEYS must be present
    and the backend block must carry either provenance fields or an
    explicit error.

    Schema policy: any ``goleft-tpu.run-manifest/1.x`` revision loads
    (minor revisions only add fields — a reader must survive
    manifests written by later versions); a different major is rejected
    with a clear error instead of being half-parsed.
    """
    with open(path) as fh:
        doc = json.load(fh)
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"manifest {path}: missing keys {missing}")
    major, _minor = parse_schema_version(doc["schema"])
    if major != SCHEMA_MAJOR:
        raise ValueError(
            f"manifest {path}: unsupported schema major version "
            f"{major} ({doc['schema']!r}); this reader supports "
            f"{SCHEMA_MAJOR}.x — upgrade goleft-tpu to read it")
    backend = doc["backend"]
    if "error" not in backend and "platform" not in backend:
        raise ValueError(
            f"manifest {path}: backend block has neither platform "
            "nor error")
    return doc
