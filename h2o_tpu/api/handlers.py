"""REST v3 route handlers (reference: water/api/*Handler.java + schemas3/).

Response shapes follow the v3 schemas (keys wrapped as {"name": ...},
__meta.schema_type, frames/models/jobs arrays) closely enough for
schema-driven clients; field coverage grows with the framework.
"""

from __future__ import annotations

import glob as globmod
import json
import os
import time
from typing import Any, Dict, List

import numpy as np

from h2o_tpu import __version__
from h2o_tpu.core.cloud import cloud
from h2o_tpu.core.frame import Frame
from h2o_tpu.core.job import Job
from h2o_tpu.core.log import recent_lines
from h2o_tpu.core.parse import parse_files, parse_setup
from h2o_tpu.models.model import Model
from h2o_tpu.models.registry import builder_class, builders
from h2o_tpu.api.server import H2OError, route
from h2o_tpu.rapids import Session, rapids_exec

_SESSIONS: Dict[str, Session] = {}
_START_TIME = time.time()


def _key(name, tpe="Key"):
    return {"name": str(name), "type": tpe, "URL": None}


# ---------------------------------------------------------------------------
# cloud / admin
# ---------------------------------------------------------------------------

@route("GET", r"/(?:3|4)/Cloud(?:\.json)?")
def cloud_status(params):
    c = cloud()
    from h2o_tpu.core.membership import monitor
    from h2o_tpu.core.memory import manager
    mem = manager().stats()
    mship = monitor().status()
    lost = set((mship.get("last_probe") or {}).get("lost") or ())
    return {
        "__meta": {"schema_version": 3, "schema_name": "CloudV3",
                   "schema_type": "Iced"},
        "version": __version__,
        "branch_name": "tpu",
        "build_number": "0",
        "build_age": "0 days",
        "build_too_old": False,
        "cloud_name": c.args.name,
        "cloud_size": c.n_nodes,
        **_backend(c),
        "cloud_uptime_millis": int((time.time() - _START_TIME) * 1000),
        # healthy = stable membership (no reform in flight, no lost
        # devices in the last liveness probe)
        "cloud_healthy": mship["state"] == "stable" and not lost,
        "consensus": True,
        # the reference locks membership forever (Paxos.java:145-166);
        # here "locked" means only "not currently re-forming"
        "locked": mship["state"] == "stable",
        "membership": mship,
        "is_client": bool(c.args.client),
        "internal_security_enabled": bool(c.args.ssl_cert),
        "nodes": [{
            "h2o": f"tpu-{i}", "ip_port": f"device:{i}",
            "healthy": i not in lost,
            "last_ping": int(time.time() * 1000), "pid": os.getpid(),
            "num_cpus": 1, "cpus_allowed": 1, "nthreads": 1,
            "my_cpu_pct": -1, "sys_cpu_pct": -1,
            # HBM accounting (core/memory.py Cleaner analog): value size =
            # resident frame bytes; swap = columns spilled to host
            "mem_value_size": mem["resident_bytes"] // c.n_nodes,
            "free_mem": max(mem["budget"] - mem["resident_bytes"], 0)
            // c.n_nodes if mem["budget"] else 0,
            "pojo_mem": 0, "swap_mem": mem["spills"],
            "num_keys": len(c.dkv.keys()),
            "max_mem": 0, "sys_load": -1.0,
        } for i in range(c.n_nodes)],
        "bad_nodes": len([i for i in lost if i < c.n_nodes]),
        "skip_ticks": False,
    }


def _backend(c) -> Dict[str, Any]:
    """What the mesh actually runs on, as JAX reports it."""
    dev = c.mesh.devices.flat[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": int(c.mesh.devices.size)}


@route("GET", r"/3/About")
def about(params):
    b = _backend(cloud())
    return {"entries": [
        {"name": "Build project version", "value": __version__},
        {"name": "Backend",
         "value": f"jax/XLA {b['platform']} ({b['device_kind']} "
                  f"x{b['device_count']})"},
    ]}


@route("GET", r"/3/Logs/nodes/(?P<node>[^/]+)/files/(?P<file>[^/]+)")
def logs(params, node, file):
    return {"log": "\n".join(recent_lines())}


@route("POST", r"/3/Shutdown")
def shutdown(params):
    """h2o.cluster().shutdown(): cancel running jobs, clear the store, and
    stop the REST server (after this response flushes) — the reference
    exits the JVM; here the cloud process may host other work, so the
    cluster's serving surface dies but the process survives."""
    import threading as _t
    from h2o_tpu.api.server import RestServer, request_context
    c = cloud()
    for job in c.jobs.list():
        if job.is_running:
            job.cancel()
    for k in list(c.dkv.keys()):
        c.dkv.remove(k, force=True)   # shutdown teardown overrides locks
    # stop the server that RECEIVED this request (not a process-global):
    # multiple live servers each shut down only themselves
    srv = getattr(request_context, "server", None) or RestServer.current
    if srv is not None:
        _t.Timer(0.5, srv.stop).start()
    return {}


def _routes_json():
    from h2o_tpu.api.server import _ROUTES
    return [{"http_method": m,
             "url_pattern": rx.pattern.strip("^$"),
             "summary": fn.__doc__ or fn.__name__,
             "input_schema": "RequestSchemaV3",
             "output_schema": "SchemaV3",
             "handler": fn.__name__} for m, rx, fn, _raw in _ROUTES]


@route("GET", r"/3/Metadata/endpoints")
def endpoints(params):
    return {"__meta": {"schema_version": 3, "schema_name": "MetadataV3",
                       "schema_type": "Metadata"},
            "routes": _routes_json()}


@route("GET", r"/3/Metadata/schemas/(?P<name>[^/]+)")
def metadata_schema(params, name):
    """Schema field metadata (water/api/SchemaMetadataV3); the h2o-py client
    defines CloudV3/H2OErrorV3/... properties from this at connect time."""
    from h2o_tpu.api import schemas
    if schemas.schema_json(name) is None:
        raise H2OError(404, f"schema {name} not found")
    return schemas.metadata_response([name])


@route("GET", r"/3/Metadata/schemas")
def metadata_schemas(params):
    from h2o_tpu.api import schemas
    return schemas.metadata_response(list(schemas.SCHEMAS),
                                     routes=_routes_json())


@route("GET", r"/3/Capabilities")
@route("GET", r"/3/Capabilities/Core")
@route("GET", r"/3/Capabilities/API")
def capabilities(params):
    """Registered extensions (water/api/CapabilitiesHandler).  The rebuild
    has no pluggable extensions; core algo surface is reported."""
    return {"__meta": {"schema_version": 3, "schema_name": "CapabilitiesV3",
                       "schema_type": "Iced"},
            "capabilities": []}


@route("GET", r"/3/Typeahead/files")
def typeahead_files(params):
    """File-path completion for import (water/api/TypeaheadHandler)."""
    src = params.get("src") or ""
    limit = int(params.get("limit", 100) or 100)
    base = os.path.expanduser(src)
    try:
        if os.path.isdir(base):
            entries = [os.path.join(base, e) for e in sorted(
                os.listdir(base))]
        else:
            d, prefix = os.path.split(base)
            entries = [os.path.join(d, e) for e in sorted(os.listdir(d or "."))
                       if e.startswith(prefix)]
    except OSError:
        entries = []
    return {"matches": entries[:limit]}


@route("POST", r"/3/InitID")
@route("GET", r"/3/InitID")
@route("POST", r"/4/sessions")
def init_id(params):
    sid = f"_sid{len(_SESSIONS) + 1:04d}"
    _SESSIONS[sid] = Session(sid)
    return {"session_key": sid}


@route("DELETE", r"/3/InitID")
def end_session(params):
    return {}


# ---------------------------------------------------------------------------
# ingest
# ---------------------------------------------------------------------------

@route("POST", r"/3/PostFile(?:\.bin)?", raw=True)
def post_file(params, body=None):
    """Single-threaded file push (water/api/PostFileHandler): the client
    sends the file contents as the raw request body
    (h2o-py/h2o/backend/connection.py _prepare_file_payload); the stream is
    spooled into ice_root and the key resolves like an imported file."""
    import shutil
    import uuid
    c = cloud()
    dest = params.get("destination_frame") or \
        f"upload_{uuid.uuid4().hex[:12]}.bin"
    # slash-free key so /3/Frames/{id} routes can address the upload
    key = dest.replace("/", "_").replace(":", "_")
    updir = os.path.join(c.args.ice_root, "uploads")
    os.makedirs(updir, exist_ok=True)
    path = os.path.join(updir, key)
    with open(path, "wb") as f:
        shutil.copyfileobj(body, f)
    c.dkv.put(key, path)
    return {"destination_frame": key,
            "total_bytes": os.path.getsize(path)}


def _import_one(path):
    """Resolve a path/glob and register nfs:// keys; (files, dests).

    Remote URIs (http/https/s3/gcs — PersistManager schemes) register
    as-is; the parser fetches them through core.persist at Parse time
    (core/parse.py localize)."""
    from h2o_tpu.core.parse import _is_remote
    if _is_remote(path):
        cloud().dkv.put(path, path)
        return [path], [path]
    matches = sorted(globmod.glob(path)) if any(ch in path for ch in "*?") \
        else ([path] if os.path.exists(path) else [])
    for p in matches:
        cloud().dkv.put(f"nfs://{p}", p)
    return matches, [f"nfs://{p}" for p in matches]


@route("POST", r"/3/ImportFilesMulti")
def import_files_multi(params):
    """h2o.lazy_import sends paths as '[p1,p2,...]'
    (water/api/ImportFilesMultiHandler)."""
    raw = params.get("paths") or ""
    paths = [p.strip() for p in str(raw).strip("[]").split(",")
             if p.strip()]
    if not paths:
        raise H2OError(400, "paths is required")
    files, dests, fails = [], [], []
    for path in paths:
        m, d = _import_one(path)
        if not m:
            fails.append(path)
        files += m
        dests += d
    if not files:
        raise H2OError(404, f"no files at {raw}")
    return {"files": files, "destination_frames": dests,
            "fails": fails, "dels": []}


@route("POST", r"/3/PutKey", raw=True)
def put_key(params, body=None):
    """Raw byte upload under an explicit key (water/api/PutKeyHandler —
    the h2o.upload_custom_metric / _put_key flow)."""
    import shutil
    c = cloud()
    dest = params.get("destination_key")
    if not dest:
        raise H2OError(400, "destination_key is required")
    overwrite = str(params.get("overwrite", "true")).lower() == "true"
    if not overwrite and c.dkv.get(dest) is not None:
        raise H2OError(400, f"key {dest} exists and overwrite=False")
    updir = os.path.join(c.args.ice_root, "uploads")
    os.makedirs(updir, exist_ok=True)
    path = os.path.join(updir,
                        dest.replace("/", "_").replace(":", "_"))
    with open(path, "wb") as f:
        shutil.copyfileobj(body, f)
    c.dkv.put(dest, path)
    # plain string (the client formats it into the 'python:key=Class'
    # custom-func reference, h2o-py/h2o/h2o.py:2226)
    return {"destination_key": dest,
            "total_bytes": os.path.getsize(path)}


@route("GET", r"/3/ImportFiles")
@route("POST", r"/3/ImportFiles")
def import_files(params):
    path = params.get("path")
    if not path:
        raise H2OError(400, "path is required")
    matches, dests = _import_one(path)
    if not matches:
        raise H2OError(404, f"no files at {path}")
    return {"files": matches, "destination_frames": dests,
            "fails": [], "dels": []}


@route("POST", r"/3/ParseSetup")
def parse_setup_route(params):
    src = _json_list(params.get("source_frames"))
    paths = [cloud().dkv.get(s) or s.replace("nfs://", "") for s in src]
    setup = parse_setup(paths, force_header=_header_directive(params))
    d = setup.to_dict()
    d.update({
        "__meta": {"schema_version": 3, "schema_name": "ParseSetupV3",
                   "schema_type": "ParseSetup"},
        "source_frames": [_key(s, "Key<Frame>") for s in src],
        "destination_frame": os.path.basename(paths[0]).replace(".", "_")
        + ".hex",
        "number_columns": len(setup.column_names),
        "parse_type": "CSV",
        "chunk_size": 4 * 1024 * 1024,
        "na_strings": [list(setup.na_strings)
                       for _ in setup.column_names],
        "single_quotes": False,
        "escapechar": None,
        "custom_non_data_line_markers": None,
        "partition_by": None,
        "skipped_columns": None,
        "warnings": [],
        "total_filtered_column_count": len(setup.column_names),
    })
    return d


_H2O_COLTYPES = {"numeric": "real", "enum": "enum", "string": "string",
                 "time": "time", "uuid": "uuid", "int": "real",
                 "real": "real", "double": "real", "float": "real",
                 "long": "real", "categorical": "enum", "factor": "enum"}


def _json_list(v):
    if isinstance(v, str):
        return json.loads(v.replace("'", '"')) if v.startswith("[") else [v]
    return v


def _header_directive(params):
    """check_header: 1 = first line is header, -1 = data, 0/None = guess."""
    ch = params.get("check_header")
    if ch is None:
        return None
    ch = int(ch)
    return True if ch == 1 else False if ch == -1 else None


@route("POST", r"/3/Parse")
def parse_route(params):
    src = _json_list(params.get("source_frames"))
    paths = [cloud().dkv.get(s) or s.replace("nfs://", "") for s in src]
    dest = params.get("destination_frame") or \
        os.path.basename(paths[0]) + ".hex"
    job = Job(dest=dest, description=f"Parse {paths}")

    # client-side overrides (h2o-py _parse_raw re-sends the possibly-edited
    # setup: column names/types, header directive, separator)
    setup = parse_setup(paths, force_header=_header_directive(params))
    if params.get("separator"):
        setup.separator = chr(int(params["separator"]))
    if params.get("column_names"):
        names = [str(n) for n in _json_list(params["column_names"])]
        if len(names) != len(setup.column_names):
            raise H2OError(400, f"column_names has {len(names)} entries, "
                                f"file has {len(setup.column_names)} "
                                "columns")
        setup.column_names = names
    if params.get("column_types"):
        types = [_H2O_COLTYPES.get(str(t).lower(), "real")
                 for t in _json_list(params["column_types"])]
        if len(types) != len(setup.column_types):
            raise H2OError(400, f"column_types has {len(types)} entries, "
                                f"file has {len(setup.column_types)} "
                                "columns")
        setup.column_types = types

    def body(j):
        fr = parse_files(paths, setup=setup, dest=dest)
        cloud().dkv.put(dest, fr)
        return fr

    cloud().jobs.start(job, body)
    job.join()  # parse is fast enough to be synchronous under the hood
    return {"job": job.to_dict(), "destination_frame": _key(dest,
                                                            "Key<Frame>")}


# ---------------------------------------------------------------------------
# frames
# ---------------------------------------------------------------------------

def _frame_schema(fr: Frame, rows: int = 10, column_offset: int = 0,
                  column_count: int = -1) -> dict:
    ncols = fr.ncols
    if column_count <= 0:
        column_count = ncols
    cols = []
    for j in range(column_offset, min(column_offset + column_count, ncols)):
        v = fr.vecs[j]
        n_head = min(rows, v.nrows)
        # slice ON DEVICE before the host transfer — a preview must not pull
        # the whole sharded column to host
        head = (np.asarray(v.data[:n_head]) if v.data is not None
                else np.asarray(v.host_data[:n_head], dtype=object))
        string_data = []
        if v.is_categorical:
            data = [None if x < 0 else int(x) for x in head]
        elif v.data is None:          # string/uuid columns live host-side
            data = []
            string_data = [None if x is None else str(x) for x in head]
        else:
            data = [None if (isinstance(x, float) and np.isnan(x))
                    else float(x) for x in head.astype(float)]
        r = v.rollups if (v.is_numeric or v.is_categorical) else None
        vtype = {"enum": "enum", "real": "real", "time": "time",
                 "string": "string"}.get(v.type, v.type)
        if vtype == "real" and r is not None and bool(r.isint):
            vtype = "int"           # H2O reports integral numerics as 'int'
        cols.append({
            "__meta": {"schema_version": 3, "schema_name": "ColV3",
                       "schema_type": "Vec"},
            "label": fr.names[j],
            "type": vtype,
            "missing_count": v.nacnt() if r else 0,
            "zero_count": int(r.zeros) if r else 0,
            "positive_infinity_count": 0, "negative_infinity_count": 0,
            "mins": [float(r.min)] if r else [],
            "maxs": [float(r.max)] if r else [],
            "mean": float(r.mean) if r else None,
            "sigma": float(r.sigma) if r else None,
            "domain": v.domain, "domain_cardinality": v.cardinality,
            "data": data, "string_data": string_data, "precision": -1,
            "histogram_bins": r.hist.tolist() if r else [],
            "histogram_base": float(r.min) if r else 0,
            "histogram_stride": float((r.max - r.min) / max(len(r.hist), 1))
            if r else 0,
        })
    return {
        "__meta": {"schema_version": 3, "schema_name": "FrameV3",
                   "schema_type": "Frame"},
        "frame_id": _key(fr.key, "Key<Frame>"),
        "byte_size": int(fr.nrows * fr.ncols * 4),
        "is_text": False,
        "row_offset": 0, "row_count": min(rows, fr.nrows),
        "column_offset": column_offset, "column_count": len(cols),
        "total_column_count": ncols,
        "checksum": 0,
        "rows": fr.nrows, "num_columns": ncols,
        "default_percentiles": [0.01, 0.1, 0.25, 0.333, 0.5, 0.667, 0.75,
                                0.9, 0.99],
        "columns": cols,
        "compatible_models": [],
        "chunk_summary": {}, "distribution_summary": {},
    }


@route("GET", r"/3/Frames")
def list_frames(params):
    dkv = cloud().dkv
    frames = [dkv.get(k) for k in dkv.keys()
              if isinstance(dkv.get(k), Frame)]
    return {"frames": [_frame_schema(f, rows=0) for f in frames]}


@route("GET", r"/3/Frames/(?P<frame_id>[^/]+)")
def get_frame(params, frame_id):
    fr = cloud().dkv.get(frame_id)
    if isinstance(fr, str) and os.path.exists(fr):
        # raw byte file from PostFile (the upload_mojo flow fetches it as
        # a pseudo 1-vec frame, like the reference's raw-file Frame)
        return {"frames": [{
            "__meta": {"schema_version": 3, "schema_name": "FrameV3",
                       "schema_type": "Frame"},
            "frame_id": _key(frame_id, "Key<Frame>"),
            "byte_size": os.path.getsize(fr), "is_text": True,
            "row_offset": 0, "row_count": 0, "column_offset": 0,
            "column_count": 0, "total_column_count": 0, "checksum": 0,
            "rows": os.path.getsize(fr), "num_columns": 0, "columns": [],
            "compatible_models": [], "chunk_summary": {},
            "distribution_summary": {},
            "default_percentiles": [],
        }]}
    if not isinstance(fr, Frame):
        raise H2OError(404, f"frame {frame_id} not found")
    rows = int(params.get("row_count", 10) or 10)
    return {"frames": [_frame_schema(
        fr, rows=rows, column_offset=int(params.get("column_offset", 0)),
        column_count=int(params.get("column_count", -1)))]}


@route("GET", r"/3/Frames/(?P<frame_id>[^/]+)/summary")
@route("GET", r"/3/Frames/(?P<frame_id>[^/]+)/light")
def frame_summary(params, frame_id):
    return get_frame(params, frame_id)


def frame_csv_chunks(fr: Frame, sep: str = ",", header: bool = True,
                     batch: int = 8192):
    """Streaming CSV chunks for a frame — shared by DownloadDataset and
    /3/Frames/{id}/export.  Column data materializes EAGERLY (a failing
    vec must 500 before the 200/header bytes go out, not truncate the
    stream mid-body); string conversion stays per batch so a multi-GB
    export never holds the full text in RSS."""
    import csv as csvmod
    import io as iomod

    def _fmt_host(x):
        return "" if x is None else str(x)

    def _fmt_time(x):
        return "" if np.isnan(x) else str(int(x))

    def _fmt_num(x):
        return "" if np.isnan(x) else (
            str(int(x)) if float(x).is_integer() else repr(float(x)))

    cols = []
    for v in fr.vecs:
        if v.host_data is not None:
            cols.append((v.host_data, _fmt_host))
        elif v.is_categorical:
            codes = np.asarray(v.to_numpy())[: fr.nrows]
            dom = v.domain or []
            cols.append((codes,
                         lambda c, dom=dom: "" if c < 0 else dom[int(c)]))
        else:
            vals = np.asarray(v.to_numpy())[: fr.nrows]
            cols.append((vals, _fmt_time if v.type == "time" else _fmt_num))

    def chunks():
        buf = iomod.StringIO()
        w = csvmod.writer(buf, delimiter=sep,
                          quoting=csvmod.QUOTE_MINIMAL)
        if header:
            w.writerow(fr.names)
            yield buf.getvalue()
            buf.seek(0)
            buf.truncate(0)
        for lo in range(0, fr.nrows, batch):
            hi = min(lo + batch, fr.nrows)
            strcols = [[fmt(x) for x in data[lo:hi]]
                       for data, fmt in cols]
            w.writerows(zip(*strcols))
            yield buf.getvalue()
            buf.seek(0)
            buf.truncate(0)
    return chunks()


@route("GET", r"/3/DownloadDataset(?:\.bin)?")
def download_dataset(params):
    """Frame -> CSV export (water/api/DownloadDataHandler); backs the
    client's as_data_frame / h2o.export_file local path."""
    frame_id = params.get("frame_id")
    fr = cloud().dkv.get(frame_id)
    if not isinstance(fr, Frame):
        raise H2OError(404, f"frame {frame_id} not found")
    return ("text/csv", frame_csv_chunks(fr))


@route("DELETE", r"/3/Frames/(?P<frame_id>[^/]+)")
def delete_frame(params, frame_id):
    cloud().dkv.remove(frame_id)
    return {}


@route("DELETE", r"/3/DKV/(?P<key>[^/]+)")
def delete_key(params, key):
    cloud().dkv.remove(key)
    return {}


@route("POST", r"/99/Rapids")
@route("POST", r"/3/Rapids")
def rapids_route(params):
    ast = params.get("ast")
    sid = params.get("session_id", "_default")
    sess = _SESSIONS.setdefault(sid, Session(sid))
    result = rapids_exec(ast, sess)
    if result is None:
        return {"key": None}
    if isinstance(result, Frame):
        # un-assigned frame results must still resolve by key afterwards
        # (h2o.rapids() callers get_frame the returned key)
        if cloud().dkv.get(str(result.key)) is not result:
            cloud().dkv.put(result.key, result)
        return {"key": _key(result.key, "Key<Frame>"),
                "num_rows": result.nrows, "num_cols": result.ncols}
    if isinstance(result, (int, float)):
        return {"scalar": float(result)}
    if isinstance(result, list):
        if result and isinstance(result[0], tuple):
            return {"string": str([x[1] for x in result])}
        # per-column numeric results (ValNums): the client accepts a list
        # in the 'scalar' slot (h2o-py/h2o/expr.py:116-117)
        return {"scalar": [float(x) for x in result]}
    return {"string": str(result)}


# ---------------------------------------------------------------------------
# model builders / models / predictions
# ---------------------------------------------------------------------------

@route("GET", r"/3/ModelBuilders")
def list_builders(params):
    out = {}
    for name, cls in builders().items():
        out[name] = {"algo": name, "algo_full_name": cls.algo,
                     "can_build": ["ALL"], "visibility": "Stable"}
    return {"model_builders": out}


def _coerce(val, default):
    if default is None:
        # untyped param (e.g. lambda_/alpha default None): numbers parse,
        # everything else passes through
        try:
            return float(val)
        except (TypeError, ValueError):
            return val
    if isinstance(default, bool):
        return str(val).lower() in ("1", "true", "yes")
    if isinstance(default, (int, float)) and not isinstance(default, bool):
        return type(default)(float(val))
    if isinstance(default, (list, tuple)):
        if isinstance(val, str):
            v = val.strip("[]")
            return [float(x) if x.strip().replace(".", "").replace(
                "-", "").isdigit() else x.strip().strip("'\"")
                for x in v.split(",") if x.strip()]
        return val
    return val


@route("POST", r"/3/ModelBuilders/(?P<algo>[^/]+)")
def build_model(params, algo):
    try:
        cls = builder_class(algo)
    except KeyError:
        raise H2OError(404, f"unknown algorithm {algo}")
    train_key = params.get("training_frame")
    fr = cloud().dkv.get(train_key) if train_key else None
    if algo == "grep" and isinstance(fr, str) and os.path.exists(
            fr.replace("nfs://", "")):
        # grep accepts a raw imported text file (hex/grep runs over
        # ByteVecs): lift the bytes into a 1-string-column frame
        from h2o_tpu.core.frame import Vec, T_STR
        with open(fr.replace("nfs://", ""), errors="replace") as f:
            lines = f.read().splitlines()
        fr = Frame(["text"], [Vec(lines, T_STR)], key=f"{train_key}_text")
    if not isinstance(fr, Frame) and algo != "generic":
        # generic (MOJO import) is the one frame-less builder
        # (hex/generic/Generic.java trains from an artifact key)
        raise H2OError(404, f"training_frame {train_key} not found")
    valid = cloud().dkv.get(params.get("validation_frame")) \
        if params.get("validation_frame") else None
    b = cls()
    # REST schema names that differ from builder keys (v3 'lambda' is a
    # Python keyword on our side)
    aliases = {"lambda": "lambda_"}
    coerced = {}
    for k, v in params.items():
        if k in ("training_frame", "validation_frame", "model_id",
                 "response_column", "ignored_columns"):
            continue
        k = aliases.get(k, k)
        if k in b.params:
            coerced[k] = _coerce(v, b.params[k])
    try:
        b._validate_fixed(coerced)   # no silently-ignored settings
    except ValueError as e:
        raise H2OError(400, str(e))
    b.params.update(coerced)
    if params.get("model_id"):
        b.model_id = params["model_id"]
    y = params.get("response_column")
    x = None
    if params.get("ignored_columns") and fr is not None:
        ign = _coerce(params["ignored_columns"], [])
        x = [c for c in fr.names if c not in ign and c != y]
    from h2o_tpu.core.tenant import AdmissionRejected, tenant_context
    tenant = params.get("tenant")
    try:
        with tenant_context(str(tenant) if tenant else None):
            job = b.train_async(x=x, y=y, training_frame=fr,
                                validation_frame=valid)
    except AdmissionRejected as e:
        # classified refusal from the fair-share admission queue —
        # the multi-tenant analog of the breaker's 429: the client
        # backs off, the cluster never wedges on an unbounded queue
        raise H2OError(429, f"admission rejected ({e.reason}): {e}",
                       headers={"Retry-After": str(max(1, int(round(
                           e.retry_after_s))))})
    return {"job": job.to_dict(),
            "messages": [], "error_count": 0,
            "parameters": {k: v for k, v in b.params.items()
                           if not str(k).startswith("_")}}


def _metrics_dict(m, frame_id=None, model_id=None):
    if m is None:
        return None
    kind_schema = {"binomial": "ModelMetricsBinomialV3",
                   "multinomial": "ModelMetricsMultinomialV3",
                   "regression": "ModelMetricsRegressionV3",
                   "clustering": "ModelMetricsClusteringV3",
                   "ordinal": "ModelMetricsOrdinalV3",
                   "anomaly": "ModelMetricsAnomalyV3",
                   "autoencoder": "ModelMetricsAutoEncoderV3",
                   }.get(m.kind, "ModelMetricsBaseV3")
    d = {"__meta": {"schema_version": 3, "schema_name": kind_schema,
                    "schema_type": "ModelMetrics"},
         "model_category": m.kind.capitalize(),
         "frame": _key(frame_id, "Key<Frame>") if frame_id else None,
         "model": _key(model_id, "Key<Model>") if model_id else None,
         "description": None, "scoring_time": 0,
         "custom_metric_name": m.data.get("custom_metric_name"),
         "custom_metric_value": m.data.get("custom_metric_value", 0.0)}
    # H2O wire casing (client metrics_base.py accessors index these
    # literally: 'MSE', 'RMSE', 'Gini', ...)
    rename = {"mse": "MSE", "rmse": "RMSE", "gini": "Gini"}
    for k, v in m.data.items():
        k = rename.get(k, k)
        if isinstance(v, np.ndarray):
            d[k] = v.tolist()
        else:
            d[k] = v
    # keys the client's printer reads unconditionally per category
    # (h2o-py/h2o/model/metrics/multinomial.py:7-57)
    if m.kind == "multinomial":
        d.setdefault("AUC", float("nan"))
        d.setdefault("pr_auc", float("nan"))
        d.setdefault("multinomial_auc_table", None)
        d.setdefault("multinomial_aucpr_table", None)
        from h2o_tpu.models.metrics import twodim_json
        cm = np.asarray(m.data.get("cm"))
        dom = [str(s) for s in (m.data.get("domain") or
                                range(cm.shape[0]))]
        rows = []
        for i in range(cm.shape[0]):
            tot = float(cm[i].sum())
            err = 1.0 - (float(cm[i, i]) / tot if tot else 0.0)
            rows.append([float(x) for x in cm[i]] +
                        [err, f"{int(tot - cm[i, i]):,} / {int(tot):,}"])
        d["cm"] = {"__meta": {"schema_version": 3,
                              "schema_name": "ConfusionMatrixV3",
                              "schema_type": "ConfusionMatrix"},
                   "table": twodim_json(
                       "Confusion Matrix", dom + ["Error", "Rate"],
                       ["long"] * len(dom) + ["double", "string"], rows,
                       "Row labels: Actual class; Column labels: "
                       "Predicted class")}
        hr = m.data.get("hit_ratios") or []
        d["hit_ratio_table"] = twodim_json(
            "Top-K Hit Ratios", ["k", "hit_ratio"], ["long", "double"],
            [[k + 1, float(v)] for k, v in enumerate(hr)])
    return d


def _cv_summary_table(summary):
    """cross_validation_metrics_summary as a TwoDimTableV3 (the client's
    ModelBase._str_items appends it verbatim; H2O renders metric rows x
    [mean, sd, cv_i_valid...] columns)."""
    if not summary:
        return None
    from h2o_tpu.api.handlers_ml import twodim
    nfold = max((len(v.get("values", [])) for v in summary.values()),
                default=0)
    cols = ["", "mean", "sd"] + [f"cv_{i+1}_valid" for i in range(nfold)]
    rows = []
    for name, v in sorted(summary.items()):
        vals = list(v.get("values", []))
        vals += [None] * (nfold - len(vals))
        rows.append([name, v.get("mean"), v.get("sd")] + vals)
    return twodim("Cross-Validation Metrics Summary", cols,
                  ["string"] + ["double"] * (len(cols) - 1), rows)


def _varimp_table(m: Model):
    """output.variable_importances as a TwoDimTableV3 (the client's
    model.varimp()/varimp_plot() read .col_header/.cell_values —
    model_base.py:708-716; h2o.varimp_heatmap and explain()'s varimp
    section gate on it)."""
    rows = None
    try:
        rows = m.varimp()
    except Exception:  # noqa: BLE001 — schema emission must not fail
        rows = None
    if not rows:
        return None
    from h2o_tpu.api.handlers_ml import twodim
    return twodim(
        "Variable Importances",
        ["Variable", "Relative Importance", "Scaled Importance",
         "Percentage"],
        ["string", "double", "double", "double"],
        [[v, rel, sc, pct] for v, rel, sc, pct in rows])


def _scoring_history_table(m: Model):
    """output.scoring_history as a TwoDimTableV3 (SharedTree
    doScoringAndSaveModel history; the client's model.scoring_history()
    and h2o.explain()'s learning_curve_plot read it).  Models trained
    without periodic scoring still get a single final-metrics row —
    reference models always score at least once."""
    out = m.output
    rows = [dict(r) for r in (out.get("scoring_history") or [])]
    if not rows:
        mm = out.get("training_metrics")
        if mm is None or "split_col" not in out and m.algo not in (
                "deeplearning", "isolationforest"):
            return None
        row = {}
        if out.get("ntrees_actual") is not None:
            row["number_of_trees"] = out.get("ntrees_actual")
        for pfx, met in (("training_", mm),
                         ("validation_", out.get("validation_metrics"))):
            if met is None:
                continue
            for k in ("mse", "logloss", "AUC", "pr_auc",
                      "mean_residual_deviance", "err", "mae",
                      "mean_anomaly_score"):
                try:
                    v = met.get(k)
                except Exception:  # noqa: BLE001
                    v = None
                if v is not None:
                    row[pfx + k.lower()] = float(v)
        rows = [row]
    for r in rows:
        for pfx in ("training_", "validation_"):
            if pfx + "mse" in r and pfx + "rmse" not in r:
                r[pfx + "rmse"] = float(r[pfx + "mse"]) ** 0.5
            if pfx + "err" in r:
                r[pfx + "classification_error"] = r.pop(pfx + "err")
            if pfx + "mean_residual_deviance" in r:
                r[pfx + "deviance"] = r.pop(pfx + "mean_residual_deviance")
    cols: list = []
    for r in rows:
        for k in r:
            if k not in cols:
                cols.append(k)
    lead = [c for c in ("timestamp", "duration", "number_of_trees",
                        "iterations", "epochs") if c in cols]
    ordered = lead + [c for c in cols if c not in lead]
    if not ordered:
        return None
    from h2o_tpu.api.handlers_ml import twodim
    return twodim("Scoring History", ordered,
                  ["string" if c == "timestamp" else "double"
                   for c in ordered],
                  [[r.get(c) for c in ordered] for r in rows])


def _model_schema(m: Model) -> dict:
    out = m.output
    return {
        "__meta": {"schema_version": 3, "schema_name": "ModelSchemaV3"},
        "model_id": _key(m.key, "Key<Model>"),
        "algo": m.algo, "algo_full_name": m.algo,
        "response_column_name": m.params.get("response_column"),
        "data_frame": _key(m.params.get("training_frame", ""),
                           "Key<Frame>"),
        "timestamp": 0,
        "parameters": _params_schema(m),
        "output": {
            "model_category": out.get("model_category") or (
                "Binomial" if out.get("response_domain") and
                len(out["response_domain"]) == 2 else
                "Multinomial" if out.get("response_domain")
                else "Regression"),
            "training_metrics": _metrics_dict(
                out.get("training_metrics")),
            "validation_metrics": _metrics_dict(
                out.get("validation_metrics")),
            # the client's ModelBase._str_items indexes these two keys
            # unconditionally (model_base.py:1978-1981)
            "cross_validation_metrics": _metrics_dict(
                out.get("cross_validation_metrics")),
            "cross_validation_metrics_summary": _cv_summary_table(
                out.get("cross_validation_metrics_summary")),
            # when CV metrics are present the client dereferences this key
            # (estimator_base.py:383) — a Key list or None
            "cross_validation_models": (
                [_key(k, "Key<Model>")
                 for k in out["cross_validation_models"]]
                if out.get("cross_validation_models") else None),
            "cross_validation_predictions": None,
            "cross_validation_holdout_predictions_frame_id": (
                _key(out["cross_validation_holdout_predictions_frame_id"],
                     "Key<Frame>")
                if out.get("cross_validation_holdout_predictions_frame_id")
                else None),
            "cross_validation_fold_assignment_frame_id": (
                _key(out["cross_validation_fold_assignment_frame_id"],
                     "Key<Frame>")
                if out.get("cross_validation_fold_assignment_frame_id")
                else None),
            "variable_importances": _varimp_table(m),
            "names": out.get("x", []),
            # parallel to "names": per-column categorical domains (the
            # client's H2OTree levels decode indexes these —
            # h2o-py/h2o/tree/tree.py:423-424)
            "domains": [
                (out.get("domains") or {}).get(c)
                for c in out.get("x", [])],
            # pre-encoding column names; h2o.explain() falls back to
            # "names" when null but the KEY must exist (_explain.py:1906)
            "original_names": None,
            "scoring_history": _scoring_history_table(m),
            "status": "DONE",
            "run_time": m.run_time_ms,
            # engine-substitution warnings (depth clamp, maxout~relu, ...)
            # — reference ModelBuilder warnings -> ModelSchemaV3
            "warnings": list(out.get("warnings") or []),
            # GLM-family models: the client's m.coef()/summary indexes it
            "coefficients_table": out.get("coefficients_table"),
        },
    }


def _params_schema(m: Model):
    """ModelParameterSchemaV3 entries.  Column params use ColSpecifierV3
    ({"column_name": ...}) and key params use KeyV3 ({"name": ...}) —
    the client's actual_params property dereferences exactly these
    shapes (model_base.py:88-95)."""
    col_params = {"response_column", "weights_column", "offset_column",
                  "fold_column", "treatment_column"}
    key_params = {"model_id", "training_frame", "validation_frame"}
    entries = []
    for k, v in m.params.items():
        if str(k).startswith("_"):
            continue
        if isinstance(v, np.ndarray):
            v = v.tolist()
        if k in col_params:
            v = {"column_name": v} if v is not None else None
        elif k in key_params:
            v = {"name": str(v)} if v is not None else None
        entries.append({"name": k, "actual_value": v})
    return entries


@route("GET", r"/3/GetGLMRegPath")
def glm_reg_path(params):
    """Regularization path of a lambda-search GLM (client:
    H2OGeneralizedLinearEstimator.getGLMRegularizationPath,
    h2o-py/h2o/estimators/glm.py:2526)."""
    m = cloud().dkv.get(params.get("model"))
    if not isinstance(m, Model):
        raise H2OError(404, f"model {params.get('model')} not found")
    rp = m.output.get("reg_path")
    if rp is None:
        raise H2OError(400, f"model {m.key} was not built with "
                            "lambda_search")
    names = list(m.output.get("coef_names", [])) + ["Intercept"]
    return {"model": _key(str(m.key), "Key<Model>"),
            "lambdas": rp["lambdas"], "alphas": rp["alphas"],
            "explained_deviance_train": rp["explained_deviance_train"],
            "explained_deviance_valid": rp["explained_deviance_valid"],
            "coefficients": rp["coefficients"],
            "coefficient_names": names,
            "coefficients_std": None, "z_values": None,
            "p_values": None, "std_errs": None}


@route("GET", r"/3/Models")
def list_models(params):
    dkv = cloud().dkv
    models = [dkv.get(k) for k in dkv.keys()
              if isinstance(dkv.get(k), Model)]
    return {"models": [_model_schema(m) for m in models]}


@route("GET", r"/3/Models/(?P<model_id>[^/]+)")
def get_model(params, model_id):
    m = cloud().dkv.get(model_id)
    if not isinstance(m, Model):
        raise H2OError(404, f"model {model_id} not found")
    return {"models": [_model_schema(m)]}


@route("DELETE", r"/3/Models/(?P<model_id>[^/]+)")
def delete_model(params, model_id):
    cloud().dkv.remove(model_id)
    return {}


# ---------------------------------------------------------------------------
# model artifacts: binary save/load + genmodel MOJO
# (water/api/ModelsHandler.java:148,259; clients: h2o-py/h2o/h2o.py
#  save_model:1501, load_model:1579, download_model/upload_model,
#  model_base.download_mojo:1165, save_mojo)
# ---------------------------------------------------------------------------

def _model_or_404(model_id) -> Model:
    m = cloud().dkv.get(model_id)
    if not isinstance(m, Model):
        raise H2OError(404, f"model {model_id} not found")
    return m


def _register_loaded(m: Model):
    cloud().dkv.put(m.key, m)
    return {"models": [{"model_id": _key(str(m.key), "Key<Model>")}]}


def _save_dest(params) -> str:
    """Validate the server-side save destination (dir/force params shared
    by the Models.bin and Models.mojo save routes)."""
    path = params.get("dir")
    if not path:
        raise H2OError(400, "dir is required")
    force = str(params.get("force", "true")).lower() == "true"
    if os.path.exists(path) and not force:
        raise H2OError(400, f"{path} exists and force=False")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    return path


@route("GET", r"/99/Models\.bin/(?P<model_id>[^/]+)")
def save_model_bin(params, model_id):
    """h2o.save_model: write the binary model server-side."""
    m = _model_or_404(model_id)
    path = _save_dest(params)
    m.save(path)
    return {"dir": path, "models": [{"model_id":
                                     _key(model_id, "Key<Model>")}]}


@route("POST", r"/99/Models\.bin/(?P<model_id>[^/]*)")
def load_model_bin(params, model_id):
    """h2o.load_model: read a binary model from a server path."""
    path = params.get("dir")
    if not path or not os.path.exists(path):
        raise H2OError(404, f"no model file at {path}")
    return _register_loaded(Model.load(path))


@route("GET", r"/3/Models\.fetch\.bin/(?P<model_id>[^/]+)")
def fetch_model_bin(params, model_id):
    """h2o.download_model: stream the binary model to the client."""
    import tempfile
    m = _model_or_404(model_id)
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "model.bin")
        m.save(p)
        with open(p, "rb") as f:
            blob = f.read()
    return ("application/octet-stream", blob,
            {"Content-Disposition":
             f'attachment; filename="{model_id}"'})


@route("POST", r"/99/Models\.upload\.bin/(?P<model_id>[^/]*)")
def upload_model_bin(params, model_id):
    """h2o.upload_model: the file arrived via POST /3/PostFile.bin; 'dir'
    is its upload key."""
    src = params.get("dir") or ""
    path = cloud().dkv.get(src) or src.replace("nfs://", "")
    if not path or not os.path.exists(str(path)):
        raise H2OError(404, f"no uploaded model at {src}")
    return _register_loaded(Model.load(str(path)))


@route("GET", r"/3/Models/(?P<model_id>[^/]+)/mojo")
def fetch_mojo(params, model_id):
    """model.download_mojo (ModelsHandler.fetchMojo:148): stream a
    genmodel-spec MOJO zip."""
    from h2o_tpu.mojo import export_genmodel_mojo
    m = _model_or_404(model_id)
    try:
        blob = export_genmodel_mojo(m)
    except NotImplementedError as e:
        raise H2OError(400, str(e))
    return ("application/zip", blob,
            {"Content-Disposition":
             f'attachment; filename="{model_id}.zip"'})


@route("GET", r"/99/Models\.mojo/(?P<model_id>[^/]+)")
def save_mojo_route(params, model_id):
    """model.save_mojo: write the MOJO zip server-side."""
    from h2o_tpu.mojo import export_genmodel_mojo
    m = _model_or_404(model_id)
    path = _save_dest(params)
    try:
        blob = export_genmodel_mojo(m)
    except NotImplementedError as e:
        raise H2OError(400, str(e))
    with open(path, "wb") as f:
        f.write(blob)
    return {"dir": path}


@route("POST", r"/3/Predictions/models/(?P<model_id>[^/]+)/frames/"
               r"(?P<frame_id>[^/]+)")
@route("POST", r"/4/Predictions/models/(?P<model_id>[^/]+)/frames/"
               r"(?P<frame_id>[^/]+)")
def predict(params, model_id, frame_id):
    """BigScore (hex/Model.java:1866): v3 scores synchronously and returns
    the predictions frame; v4 returns a Job the client polls (the h2o-py
    model_base.predict path)."""
    m = cloud().dkv.get(model_id)
    fr = cloud().dkv.get(frame_id)
    if not isinstance(m, Model):
        raise H2OError(404, f"model {model_id} not found")
    if not isinstance(fr, Frame):
        raise H2OError(404, f"frame {frame_id} not found")
    dest = params.get("predictions_frame") or f"predictions_{model_id}" \
        f"_{frame_id}"
    def flag(name):
        return str(params.get(name, "")).lower() == "true"

    recon = flag("reconstruction_error")
    per_feature = flag("reconstruction_error_per_feature")

    contribs = flag("predict_contributions")
    leaf_assign = flag("leaf_node_assignment")
    staged = flag("predict_staged_proba")
    job = Job(dest=dest, description=f"predict {model_id} on {frame_id}")

    def body(j):
        if recon:
            # autoencoder anomaly scoring (DeepLearningModel.anomaly;
            # client: h2o-py/h2o/model/models/autoencoder.py:42)
            if not m.output.get("autoencoder"):
                raise H2OError(400, f"model {model_id} is not an "
                                    "autoencoder")
            pf = m.anomaly(fr, per_feature=per_feature)
        elif contribs:
            # TreeSHAP (ModelMetricsHandler.predictContributions; the
            # client's model.predict_contributions v4 job flow)
            def opt_n(name):
                v = params.get(name)
                return 0 if v in (None, "", "None") else int(v)
            try:
                pf = m.predict_contributions(
                    fr, top_n=opt_n("top_n"), bottom_n=opt_n("bottom_n"),
                    compare_abs=flag("compare_abs"),
                    output_format=params.get(
                        "predict_contributions_output_format",
                        "Original") or "Original")
            except NotImplementedError as e:
                raise H2OError(400, str(e))
        elif leaf_assign:
            t = params.get("leaf_node_assignment_type") or "Path"
            try:
                pf = m.predict_leaf_node_assignment(fr, assign_type=t)
            except NotImplementedError as e:
                raise H2OError(400, str(e))
        elif staged:
            try:
                pf = m.staged_predict_proba(fr)
            except NotImplementedError as e:
                raise H2OError(400, str(e))
        else:
            pf = m.predict(fr)
        pf.key = dest
        cloud().dkv.put(dest, pf)
        return pf

    cloud().jobs.start(job, body)
    job.join()  # raises on FAILED
    return {"job": job.to_dict(),
            "predictions_frame": _key(dest, "Key<Frame>"),
            "model_metrics": [{"predictions":
                               {"frame_id": _key(dest, "Key<Frame>")}}]}


@route("POST", r"/3/ModelMetrics/models/(?P<model_id>[^/]+)/frames/"
               r"(?P<frame_id>[^/]+)")
def model_metrics(params, model_id, frame_id):
    m = cloud().dkv.get(model_id)
    fr = cloud().dkv.get(frame_id)
    if not isinstance(m, Model) or not isinstance(fr, Frame):
        raise H2OError(404, "model or frame not found")
    mm = m.model_metrics(fr)
    from h2o_tpu.api.handlers_models import record_metrics
    record_metrics(model_id, frame_id, mm)
    return {"model_metrics": [_metrics_dict(mm, frame_id=frame_id,
                                            model_id=model_id)]}


# ---------------------------------------------------------------------------
# jobs
# ---------------------------------------------------------------------------

@route("GET", r"/3/Jobs")
def list_jobs(params):
    return {"jobs": [j.to_dict() for j in cloud().jobs.list()]}


@route("GET", r"/3/Jobs/(?P<job_id>[^/]+)")
def get_job(params, job_id):
    j = cloud().jobs.get(job_id)
    if j is None:
        raise H2OError(404, f"job {job_id} not found")
    return {"jobs": [j.to_dict()]}


@route("POST", r"/3/Jobs/(?P<job_id>[^/]+)/cancel")
def cancel_job(params, job_id):
    j = cloud().jobs.get(job_id)
    if j is None:
        raise H2OError(404, f"job {job_id} not found")
    j.cancel()
    return {}


# -- diagnostics + recovery routes (SURVEY §5.1, §5.3) ----------------------

@route("GET", r"/3/Timeline")
def timeline(params):
    from h2o_tpu.core.diag import TimeLine
    return {"events": TimeLine.snapshot()}


@route("GET", r"/3/WaterMeterCpuTicks/(?P<node>[^/]+)")
@route("GET", r"/3/WaterMeterCpuTicks")
def water_meter_cpu(params, node=None):
    from h2o_tpu.core.diag import water_meter_cpu_ticks
    return water_meter_cpu_ticks()


@route("GET", r"/3/WaterMeterIo")
def water_meter_io_route(params):
    from h2o_tpu.core.diag import water_meter_io
    return water_meter_io()


@route("GET", r"/3/JStack")
def jstack_route(params):
    from h2o_tpu.core.diag import jstack
    return {"traces": jstack()}


@route("POST", r"/3/Profiler")
@route("GET", r"/3/Profiler")
def profiler_route(params):
    from h2o_tpu.core.diag import Profiler
    secs = float(params.get("duration_secs", 0.5))
    p = Profiler().start()
    time.sleep(min(secs, 10.0))
    counts = p.stop()
    top = [{"frame": k, "hits": v}
           for k, v in list(counts.items())[:100]]
    return {"profile": top}


@route("GET", r"/3/DeviceMemory")
def device_memory_route(params):
    from h2o_tpu.core.diag import device_memory
    return {"devices": device_memory()}


@route("GET", r"/3/Dispatch")
def dispatch_route(params):
    """Data-plane dispatch observability: per-phase compile/dispatch/
    transfer counters (core/diag.DispatchStats) plus the unified
    executable store's totals (core/exec_store.py) — the numbers that
    prove steady-state training recompiles nothing AND that a fresh
    process warmed its kernel set from disk.

    ``store`` carries size (entries/capacity), the persistent-AOT layer
    (disk_hits / disk_stores / serialized bytes written+read /
    serialize_unsupported fallbacks), and eviction counts; ``cache`` is
    the same stats block under the PR 3 name for older clients.  The
    ``munge`` phase covers the device-resident sort/merge/group-by/
    filter kernels (core/munge.py); ``host_pulls``/``host_pull_bytes``
    count Vec payload device->host materializations per phase — the
    munge row must stay at zero while the verbs run on device.

    ``plan`` reports the lazy Rapids planner (rapids/plan.py): regions
    considered/fused, verbs folded into fused programs, repacks and
    host count-syncs elided versus the eager per-verb path, OOM
    degradations to the unfused chain, and the fuse-lever split.

    ``dispatch.collectives`` is the per-phase collective byte ledger
    from the two-level mesh helpers (core/cloud.py hpsum/hall_gather/
    hall_to_all): per collective kind:tag, the trace-time inner-ICI
    vs outer-DCN byte estimates per compiled program — the numbers
    tests/test_two_level_mesh.py asserts are O(table) across DCN."""
    from h2o_tpu.core.diag import DispatchStats
    from h2o_tpu.core.exec_store import exec_store
    from h2o_tpu.rapids.plan import PlanStats
    s = exec_store().stats()
    return {"dispatch": DispatchStats.snapshot(),
            "cache": s, "store": s,
            "plan": PlanStats.snapshot()}


@route("GET", r"/3/Recovery")
def recovery_list(params):
    """Pending recovery snapshots, with iteration-checkpoint state
    (trees/steps done so far) so clients can see HOW FAR a crashed job
    got before deciding to resume it."""
    from h2o_tpu.core.recovery import pending_recoveries
    d = params.get("recovery_dir") or cloud().args.auto_recovery_dir
    if not d:
        raise H2OError(400, "recovery_dir required (no auto_recovery_dir "
                            "configured)")
    out = []
    for info in pending_recoveries(d):
        out.append({
            "kind": info.get("kind"), "job_id": info.get("job_id"),
            "dir": info.get("dir"), "started": info.get("started"),
            "models_done": len(info.get("models") or ()),
            "has_iteration_checkpoint":
                bool(info.get("has_iteration_checkpoint")),
            "iteration": info.get("iteration")})
    return {"recovery_dir": d, "pending": out}


@route("GET", r"/3/Resilience")
def resilience_stats(params):
    """Retry/chaos/watchdog/OOM observability: cumulative retry
    counters (core/resilience.py), the FULL injected-fault counter set
    (core/chaos.py — one dedicated counter per injector,
    lint-enforced), the job watchdog's expiry/eviction totals, the OOM
    degradation-ladder state (core/oom.py: oom_events, sweeps,
    degradations per site/rung), the HBM memory-manager accounting and
    the elastic-membership state with its per-reform event history
    (core/membership.py: cause, old/new mesh, jobs interrupted/resumed,
    duration) — the numbers the chaos soak harness asserts against.
    The ``memory`` block carries the tiered-column-store telemetry
    (core/memory.py MemoryManager.stats()): per-tier resident bytes
    (``tiers.hbm/host/persist``), ``peak_hbm_bytes``, block paging
    counters (``pages_in``/``pages_out``, ``persists``/
    ``persist_reloads``) and the streaming prefetcher's
    ``prefetch_hits``/``prefetch_misses``/``demand_page_stalls``.
    The ``serving`` block carries the serve-fleet protection state
    (serve/registry.serving_stats): process-wide ``breaker_trips``/
    ``breaker_sheds``/``breaker_half_opens``/``breaker_closes``,
    ``canary_rollbacks`` and ``shadow_mismatches`` totals, and each
    deployment's current breaker state and queue depth."""
    from h2o_tpu.core import oom, resilience
    from h2o_tpu.core.chaos import chaos
    from h2o_tpu.core.membership import monitor
    from h2o_tpu.core.memory import manager
    from h2o_tpu.core.tenant import list_tenants
    from h2o_tpu.serve.registry import serving_stats
    jr = cloud().jobs
    c = chaos()
    mem = manager().stats()
    # join the per-tag residency the manager published (it never reads
    # the DKV under its own lock) with each tenant's configured share
    tenants = {t.name: t.to_dict() for t in list_tenants()}
    for tag, row in (mem.get("tenants") or {}).items():
        if tag in tenants:
            tenants[tag]["memory"] = row
    admission = (jr._admission.stats() if jr._admission is not None
                 else None)
    return {
        "retry": resilience.stats(),
        "chaos": dict(enabled=c.enabled, **c.counters()),
        "oom": oom.stats(),
        "memory": mem,
        "membership": monitor().payload(),
        "serving": serving_stats(),
        "tenants": tenants,
        "admission": admission,
        "watchdog": {"expired_jobs": jr.expired_count,
                     "evicted_jobs": jr.evicted_count,
                     "default_deadline_secs": jr.default_deadline_secs,
                     "default_stall_secs": jr.default_stall_secs,
                     "jobs_cap": jr.jobs_cap},
    }


@route("GET", r"/3/Autotune")
def autotune_route(params):
    """Kernel-autotuner observability (core/autotune.py): the active
    mode and backend, every registered lever (site, env knob, candidate
    variants, forced override if any), the decision table loaded this
    process — winner, per-candidate probe timings / parity verdicts,
    source (probe vs disk) — and the probe/disk counters the subprocess
    zero-probe drill asserts against."""
    from h2o_tpu.core.autotune import autotune_payload
    return autotune_payload()


@route("GET", r"/3/Audit")
def audit_route(params):
    """graftaudit observability (lint/audit.py + core/lockwitness.py):
    which tiers are live (``H2O_TPU_AUDIT`` for the IR executable
    auditor, ``H2O_TPU_LOCK_WITNESS`` for the runtime lock witness),
    the GL7xx/GL8xx findings computed from THIS process's recorders,
    the witnessed lock-acquisition graph cross-checked against
    graftlint's static GL402 edges (witnessed_only / static_only),
    any acquisition-order cycles with their captured stacks, held-lock
    device dispatches, and per-site compile/aval-churn counters."""
    from h2o_tpu.lint.audit import audit_payload
    return audit_payload()


@route("POST", r"/3/Recovery/resume")
def recovery_resume(params):
    """Asynchronous resume: returns a job key immediately, the recovery
    trains in the background (the reference returns the resumed job)."""
    from h2o_tpu.core.job import Job
    from h2o_tpu.core.recovery import auto_recover, pending_recoveries
    from h2o_tpu.core.store import Key
    d = params.get("recovery_dir")
    if not d:
        raise H2OError(400, "recovery_dir required")
    pending = pending_recoveries(d)
    job = Job(dest=Key.make("recovery"),
              description=f"auto-recover {len(pending)} job(s) from {d}",
              priority=Job.SYSTEM_PRIORITY)
    cloud().jobs.start(job, lambda j: auto_recover(d))
    return {"job": {"key": {"name": str(job.key)}},
            "pending": len(pending)}


@route("POST", r"/3/Frames/(?P<frame_id>[^/]+)/export")
def frame_export(params, frame_id):
    """h2o.export_file (FramesHandler.export + ExportFileTsk): write the
    frame as CSV (or parquet) at a server-side path; the client wraps
    the response in H2OJob and polls it."""
    import os as _os
    fr = cloud().dkv.get(frame_id)
    if not isinstance(fr, Frame):
        raise H2OError(404, f"frame {frame_id} not found")
    path = params.get("path")
    if not path:
        raise H2OError(400, "path required")
    force = str(params.get("force", "")).lower() == "true"
    parts = int(params.get("num_parts") or 1)
    fmt = (params.get("format") or "csv").lower()
    sep = params.get("separator") or ","
    if sep.isdigit():                  # the client sends ord(sep)
        sep = chr(int(sep))
    if parts not in (1, -1):
        raise H2OError(400, "multi-part export (num_parts > 1) is not "
                            "supported; use num_parts=1")
    if fmt not in ("csv", "parquet"):
        raise H2OError(400, f"unsupported export format {fmt!r}")
    remote = "://" in path and path.split("://", 1)[0] not in ("file",
                                                              "nfs")
    local = path[7:] if path.startswith("file://") else path
    if not remote and _os.path.exists(local) and not force:
        raise H2OError(400, f"{path} exists; use force=True to "
                            "overwrite")
    # exports are control-plane work: the reserved system pool keeps
    # them from starving behind long model builds (core/job.py)
    job = Job(dest=path, description=f"Export frame {frame_id}",
              priority=Job.SYSTEM_PRIORITY)

    def body(j):
        if fmt == "parquet":
            import io as iomod
            import pandas as pd
            import pyarrow as pa
            import pyarrow.parquet as pq
            data = {}
            for n, v in zip(fr.names, fr.vecs):
                if v.host_data is not None:
                    data[n] = list(v.host_data)
                elif v.is_categorical:
                    codes = np.asarray(v.to_numpy())[: fr.nrows]
                    dom = v.domain or []
                    data[n] = [None if c < 0 else dom[int(c)]
                               for c in codes]
                else:
                    data[n] = np.asarray(v.to_numpy())[: fr.nrows]
            tbl = pa.Table.from_pandas(pd.DataFrame(data))
            if remote:
                buf = iomod.BytesIO()
                pq.write_table(tbl, buf)
                from h2o_tpu.core.persist import write_bytes
                write_bytes(path.rstrip("/") + "/part-0.parquet",
                            buf.getvalue())
            else:
                if force and _os.path.isfile(local):
                    _os.unlink(local)   # format change: file -> dir
                _os.makedirs(local, exist_ok=True)
                pq.write_table(tbl, _os.path.join(local,
                                                  "part-0.parquet"))
        elif remote:
            # scheme URIs (s3/gcs/hdfs/http) go through the persist
            # byte stores exactly like save_frame does
            from h2o_tpu.core.persist import write_bytes
            write_bytes(path,
                        "".join(frame_csv_chunks(fr, sep=sep)).encode())
        else:
            if force and _os.path.isdir(local):
                import shutil as _sh   # format change: dir -> file
                _sh.rmtree(local)
            with open(local, "w", newline="") as f:
                for chunk in frame_csv_chunks(fr, sep=sep):
                    f.write(chunk)
        return path

    cloud().jobs.start(job, body)
    job.join()
    return {"job": job.to_dict(), "path": path}


@route("POST", r"/3/Frames/load")
def frame_load(params):
    from h2o_tpu.core.persist import load_frame
    path = params.get("dir")
    if not path:
        raise H2OError(400, "dir required")
    fr = load_frame(path)
    cloud().dkv.put(fr.key, fr)
    return {"frame_id": str(fr.key), "rows": fr.nrows,
            "columns": fr.ncols}


# v99 ML orchestration routes (Grid / AutoML / Leaderboards) live in their
# own module; importing registers them on the shared route table.
from h2o_tpu.api import handlers_ml  # noqa: E402,F401
from h2o_tpu.api import handlers_frames  # noqa: E402,F401
from h2o_tpu.api import handlers_ext  # noqa: E402,F401
from h2o_tpu.api import handlers_models  # noqa: E402,F401
from h2o_tpu.api import handlers_serving  # noqa: E402,F401
from h2o_tpu.api import handlers_stream  # noqa: E402,F401
from h2o_tpu.api import handlers_tenant  # noqa: E402,F401
from h2o_tpu.api import handlers_transforms  # noqa: E402,F401
from h2o_tpu.api import handlers_analysis  # noqa: E402,F401
from h2o_tpu.api import flow_ui  # noqa: E402
flow_ui.register_routes()
