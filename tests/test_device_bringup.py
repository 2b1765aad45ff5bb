"""One owner per chip, no hidden CPU landing, a placeable compile cache.

These run their subjects in FRESH interpreters: the pytest process has
long since initialised a JAX backend (conftest.py), and the whole point
is what happens in a process that has not."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code, env=None, cwd=REPO, timeout=300):
    full = dict(os.environ)
    full["PYTHONPATH"] = REPO + os.pathsep + full.get("PYTHONPATH", "")
    full.update(env or {})
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=cwd, env=full)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


# -- the compile cache --------------------------------------------------------

_WHERE = (
    "import jax\n"
    "from parca_agent_tpu.runtime import compile_cache\n"
    "calls = []\n"
    "real = jax.config.update\n"
    "def spy(name, value):\n"
    "    calls.append(name)\n"
    "    return real(name, value)\n"
    "jax.config.update = spy\n"
    "where = compile_cache.configure()\n"
    "import json\n"
    "from jax._src import xla_bridge\n"
    "print(json.dumps({'where': where, 'set': calls,\n"
    "  'config': jax.config.jax_compilation_cache_dir,\n"
    "  'backend': xla_bridge.backends_are_initialized()}))\n")


def test_cache_dir_placed_from_outside_is_never_set_in_code(tmp_path):
    placed = str(tmp_path / "placed")
    got = json.loads(_python(
        _WHERE, env={"JAX_COMPILATION_CACHE_DIR": placed}))
    assert got["where"] == placed
    assert "jax_compilation_cache_dir" not in got["set"]  # no setter ran
    assert got["config"] == placed                        # JAX read it
    assert got["backend"] is False


def test_default_cache_dir_is_the_same_checkout_path_from_any_process(
        tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": ""}
    a = json.loads(_python(_WHERE, env=env))
    b = json.loads(_python(_WHERE, env=env, cwd=str(tmp_path)))
    want = os.path.join(REPO, ".jax_cache")
    assert a["where"] == b["where"] == a["config"] == b["config"] == want
    assert a["set"].count("jax_compilation_cache_dir") == 1
    assert a["backend"] is False  # placing the cache starts no backend
    # The one place in the tree that sets it is the helper.
    hits = subprocess.run(
        ["grep", "-rlE", r"jax_compilation_cache_dir|compilation_cache_dir\(",
         "--include=*.py", "parca_agent_tpu", "chip_smoke.py"],
        capture_output=True, text=True, cwd=REPO).stdout.split()
    assert hits == ["parca_agent_tpu/runtime/compile_cache.py"]


# -- identity never initialises a backend -------------------------------------

def test_scrape_while_probing_starts_no_backend_and_latches_nothing():
    """/metrics and /debug/device land while the bring-up probe child
    holds the chip: rendering them must leave jax's backends empty (a
    parent that initialised now would land on XLA:CPU for life) and
    must not latch a placeholder identity. Once the owner has learned
    the identity, the same scrape serves it."""
    out = _python(
        "import sys, threading\n"
        "from parca_agent_tpu.runtime.device_health import "
        "DeviceHealthRegistry\n"
        "from parca_agent_tpu.runtime.device_telemetry import "
        "DeviceTelemetry, backend_initialized\n"
        "from parca_agent_tpu.web import render_metrics\n"
        "release = threading.Event()\n"
        "def probe():\n"
        "    release.wait(60)\n"
        "    return True, 'ok on cpu', 'cpu'\n"
        "tel = DeviceTelemetry()\n"
        "reg = DeviceHealthRegistry(probe=probe, probe_timeout_s=60)\n"
        "reg.start()\n"
        "assert reg.state == 'probing'\n"
        "for _ in range(3):\n"
        "    m = render_metrics([], device_health=reg, "
        "device_telemetry=tel)\n"
        "    assert 'parca_agent_device_info' not in m\n"
        "    assert tel.ensure_identity() == {}\n"
        "    assert tel.snapshot()['identity'] == {}\n"
        "assert not backend_initialized()\n"
        "xb = sys.modules.get('jax._src.xla_bridge')\n"
        "assert xb is None or not xb._backends, 'a backend was created'\n"
        "assert tel._identity is None, 'a placeholder was latched'\n"
        "release.set()\n"
        "assert reg.wait_bringup(30) and reg.state == 'healthy'\n"
        "from parca_agent_tpu.runtime.device_telemetry import "
        "collect_identity\n"
        "tel.set_identity(collect_identity())\n"
        "m = render_metrics([], device_health=reg, device_telemetry=tel)\n"
        "assert 'parca_agent_device_info{' in m and 'platform=\"cpu\"' in m\n"
        "print('ok')\n", env={"JAX_PLATFORMS": "cpu"})
    assert out.strip() == "ok"


# -- the agent: nothing touches JAX while the probe child is alive ------------

_CLI = (
    "import sys, threading, time, urllib.request\n"
    "from parca_agent_tpu.capture.formats import save_snapshot\n"
    "from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate\n"
    "from parca_agent_tpu.runtime import device_health as dh\n"
    "from parca_agent_tpu.runtime.device_telemetry import "
    "backend_initialized\n"
    "seen = {'children': 0, 'overlap': False, 'scrapes': 0}\n"
    "real = dh.subprocess_probe\n"
    "def watched(timeout_s, *a, **kw):\n"
    "    # Sample the parent for as long as the real child is alive.\n"
    "    box = {}\n"
    "    t = threading.Thread(target=lambda: box.update(r=real(timeout_s)))\n"
    "    t.start()\n"
    "    while t.is_alive():\n"
    "        seen['overlap'] |= backend_initialized()\n"
    "        time.sleep(0.01)\n"
    "    seen['overlap'] |= backend_initialized()\n"
    "    seen['children'] += 1\n"
    "    return box['r']\n"
    "dh.subprocess_probe = watched\n"
    "stop = threading.Event()\n"
    "def hammer(port):\n"
    "    while not stop.is_set():\n"
    "        try:\n"
    "            urllib.request.urlopen(\n"
    "                f'http://127.0.0.1:{port}/metrics', timeout=1).read()\n"
    "            seen['scrapes'] += 1\n"
    "        except OSError:\n"
    "            pass\n"
    "        time.sleep(0.02)\n"
    "import socket\n"
    "s = socket.socket(); s.bind(('127.0.0.1', 0)); port = s.getsockname()[1]\n"
    "s.close()\n"
    "threading.Thread(target=hammer, args=(port,), daemon=True).start()\n"
    "snap = generate(SyntheticSpec(n_pids=6, n_unique_stacks=64, n_rows=64,\n"
    "                              total_samples=640, seed=3))\n"
    "save_snapshot(snap, 'w.snap')\n"
    "from parca_agent_tpu.cli import run\n"
    "rc = run(['--capture', 'replay', '--replay', 'w.snap', 'w.snap',\n"
    "          '--aggregator', AGG, '--fast-encode',\n"
    "          '--profiling-duration', '1',\n"
    "          '--local-store-directory', 'out',\n"
    "          '--http-address', f'127.0.0.1:{port}',\n"
    "          '--debuginfo-upload-disable', '--node', 'n'])\n"
    "stop.set()\n"
    "assert rc == 0, rc\n"
    "assert seen['children'] == 1, seen   # bring-up ran in a child, once\n"
    "assert not seen['overlap'], 'JAX initialised while the child lived'\n"
    "assert backend_initialized()         # ...and only afterwards\n"
    "import json; print(json.dumps(seen))\n")


def _cli(aggregator, tmp_path):
    out = _python(f"AGG = {aggregator!r}\n" + _CLI, cwd=str(tmp_path),
                  env={"JAX_PLATFORMS": "cpu",
                       "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    return json.loads(out.strip().splitlines()[-1])


def test_agent_stays_off_jax_while_its_probe_child_is_alive(tmp_path):
    """The dict path, scraped from t=0: the bring-up probe is a child,
    the parent initialises JAX only after that child has exited, and
    both windows then run on the device (no fallback window raced a
    healthy probe)."""
    seen = _cli("dict", tmp_path)
    assert seen["scrapes"] >= 1
    import gzip

    from parca_agent_tpu.pprof.builder import parse_pprof

    total = 0
    for f in (tmp_path / "out").iterdir():
        p = parse_pprof(gzip.decompress(f.read_bytes()))
        total += sum(v[0] for _, v, _ in p.samples)
    assert total == 2 * 640


def test_sharded_asks_for_devices_only_after_the_probe_child(tmp_path):
    """--aggregator sharded used to call jax.devices() BEFORE starting
    the probe, so its probe child could never get the chip."""
    _cli("sharded", tmp_path)
