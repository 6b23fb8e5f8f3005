"""The load generator: a standard-library child that keeps its schedule."""
import ast
import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Echo(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body.get("sleep"):
            time.sleep(body["sleep"])
        status = body.get("status", 200)
        out = json.dumps({"echo": body, "path": self.path}).encode()
        self.send_response(status)
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)


def _serve():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _run(plan):
    p = subprocess.run([sys.executable, os.path.join(BENCH, "loadgen.py")],
                       input=json.dumps(plan).encode(), capture_output=True,
                       timeout=60, check=True)
    return json.loads(p.stdout)["results"]


def test_imports_only_the_standard_library():
    tree = ast.parse(open(os.path.join(BENCH, "loadgen.py")).read())
    mods = {a.name.split(".")[0] for node in ast.walk(tree)
            if isinstance(node, ast.Import) for a in node.names}
    mods |= {node.module.split(".")[0] for node in ast.walk(tree)
             if isinstance(node, ast.ImportFrom) and node.module}
    assert mods <= set(sys.stdlib_module_names) | {"__future__"}


def test_open_loop_schedule_timing_and_statuses():
    srv = _serve()
    try:
        t0 = time.monotonic() + 0.5
        reqs = [{"t": 0.1 * i, "path": "/query", "body": {"i": i}}
                for i in range(10)]
        reqs.append({"t": 0.2, "path": "/update",
                     "body": {"status": 429}})
        reqs.append({"t": 0.3, "path": "/query",
                     "body": {"sleep": 0.4}})
        res = _run({"host": "127.0.0.1", "port": srv.server_address[1],
                    "t0": t0, "workers": 8, "give_up": 10.0,
                    "requests": reqs})
    finally:
        srv.shutdown()
    assert len(res) == len(reqs)
    for r, q in zip(res, reqs):
        assert abs(r["t_sched"] - (t0 + q["t"])) < 1e-9
        assert r["t_send"] >= r["t_sched"] - 1e-3  # never early
        assert r["t_send"] - r["t_sched"] < 0.25  # nor much late here
        assert r["t_recv"] >= r["t_send"]
    assert [r["status"] for r in res[:10]] == [200] * 10
    assert res[3]["body"]["echo"] == {"i": 3}
    assert res[10]["status"] == 429 and res[10]["body"]["path"] == "/update"
    # an open loop does not wait: the slow request did not delay the rest
    assert res[11]["t_recv"] - res[11]["t_sched"] >= 0.4
    assert res[4]["t_send"] < res[11]["t_recv"]


def test_unanswered_requests_are_given_up():
    res = _run({"host": "127.0.0.1", "port": 9, "t0": time.monotonic(),
                "workers": 2, "give_up": 2.0,
                "requests": [{"t": 0.0, "path": "/query", "body": {}}]})
    assert res[0]["status"] == 0 and res[0]["t_recv"] is None
