from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
from typing import Callable, Dict, List, Optional, Sequence

from horovod_tpu.common.basics import pick_free_port


@dataclasses.dataclass
class RankResult:
    rank: int
    returncode: int
    stdout: str
    stderr: str
    # True on the rank the launcher saw fail FIRST — the one whose error is
    # the real one; later nonzero exits are usually the kill cascade.
    first_failure: bool = False


def signal_name(returncode: int) -> str:
    """Human label for a rank exit code: 'SIGKILL (signal 9)' for signal
    deaths (negative returncodes, the subprocess convention), or the plain
    code otherwise."""
    if returncode >= 0:
        return str(returncode)
    import signal

    try:
        name = signal.Signals(-returncode).name
    except ValueError:
        name = f"signal {-returncode}"
    return f"{name} (signal {-returncode})"


def failure_report(results, tail_lines: int = 30,
                   postmortem_dir: Optional[str] = None) -> str:
    """One-stop failure summary: every failing rank labeled (signal names
    included), then the FIRST-failing rank's stderr tail — the root cause,
    ahead of the kill cascade's -9 noise.  With a postmortem dir set
    (``--postmortem-dir`` / ``HVD_TPU_POSTMORTEM_DIR``), points at the
    first-failing rank's dump and repeats the coordinator's cross-rank
    diagnosis next to the tail."""
    lines = []
    first = None
    for r in results:
        if r.returncode == 0:
            continue
        marker = "  <- first failure" if r.first_failure else ""
        lines.append(
            f"rank {r.rank} exited with {signal_name(r.returncode)}{marker}")
        if r.first_failure:
            first = r
    if first is None:  # no flagged rank (e.g. all died in the same sweep)
        first = next((r for r in results if r.returncode != 0), None)
    if first is not None and first.stderr:
        tail = first.stderr.strip().splitlines()[-tail_lines:]
        lines.append(f"--- rank {first.rank} stderr (last {len(tail)} "
                     f"lines) ---")
        lines.extend(tail)
    directory = (postmortem_dir
                 or os.environ.get("HVD_TPU_POSTMORTEM_DIR") or "")
    if first is not None and directory:
        lines.extend(_postmortem_lines(directory, first.rank))
    return "\n".join(lines)


def _postmortem_lines(directory: str, first_rank: int) -> List[str]:
    """Postmortem pointers for the failure report: the first-failing
    rank's dump path (a crashed-before-init rank may have none — fall
    back to any rank's) and the cross-rank diagnosis, read from whichever
    dump carries it (the coordinator broadcast it to every survivor)."""
    import glob
    import json

    from horovod_tpu.common import postmortem as _postmortem

    lines: List[str] = []
    path = _postmortem.dump_path_for(directory, first_rank)
    all_dumps = sorted(glob.glob(os.path.join(directory, "rank-*.json")))
    if path is None and all_dumps:
        path = all_dumps[0]
    if path is None:
        return lines
    lines.append(f"postmortem: {path}"
                 + (f" (+{len(all_dumps) - 1} more rank dump(s); render "
                    f"with tools/postmortem_dump.py {directory})"
                    if len(all_dumps) > 1 else ""))
    diagnosis = None
    transport = None
    for candidate in ([path] + [p for p in all_dumps if p != path]):
        try:
            with open(candidate) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if transport is None:
            transport = doc.get("transport")
        if diagnosis is None:
            diagnosis = doc.get("diagnosis")
        if diagnosis and transport:
            break
    if diagnosis:
        lines.append(f"cross-rank diagnosis: {diagnosis}")
    # Which data-plane transport each link ran on when the rank died
    # (docs/performance.md#transport): a fault on a same-host link behaves
    # differently over shm rings than over TCP sockets, so the report
    # names the active path per peer up front.
    if transport:
        peers = transport.get("peers") or {}
        peer_part = ("  peers: " + "  ".join(
            f"{p}={peers[p]}" for p in sorted(
                peers, key=lambda x: int(x) if x.isdigit() else 0))
            if peers else "")
        lines.append(f"transport: local hops on "
                     f"{transport.get('local', 'tcp')}{peer_part}")
    return lines


def _shm_job_prefix(coord: str) -> str:
    """FNV-1a-32 of the coordinator endpoint, matching the engine's
    ``ShmSegmentName`` (engine/cc/transport.cc): every shared-memory
    segment a job keyed on this coordinator can create is named
    ``hvdtpu_<hash>_n<node>_e<epoch>`` under /dev/shm."""
    h = 2166136261
    for b in coord.encode():
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return f"hvdtpu_{h:08x}_"


def sweep_shm_segments(coord: str) -> List[str]:
    """Unlink every /dev/shm segment left by the job keyed on ``coord``;
    returns the names removed.  The engine unlinks its own segment the
    moment all local ranks have attached, and again on every typed-death
    path, so residue is only possible when a rank dies inside the narrow
    create-to-attach window (e.g. SIGKILL from an injected crash).  The
    launcher sweeps after every attempt — success included, where it is a
    no-op — so even that window cannot leak across a --max-restarts
    relaunch or past job exit.  Local filesystem only: remote (ssh) ranks
    rely on the engine's own unlink paths."""
    removed: List[str] = []
    prefix = _shm_job_prefix(coord)
    try:
        names = os.listdir("/dev/shm")
    except OSError:
        return removed
    for name in names:
        if name.startswith(prefix):
            try:
                os.unlink(os.path.join("/dev/shm", name))
                removed.append(name)
            except OSError:
                pass
    return removed


def make_rank_env(rank: int, size: int, coord: str, data: Sequence[str],
                  base_env: Optional[Dict[str, str]] = None,
                  local_rank: Optional[int] = None,
                  local_size: Optional[int] = None,
                  xla_coord: Optional[str] = None) -> Dict[str, str]:
    env = dict(base_env if base_env is not None else os.environ)
    env["HVD_TPU_RANK"] = str(rank)
    env["HVD_TPU_SIZE"] = str(size)
    env["HVD_TPU_LOCAL_RANK"] = str(local_rank if local_rank is not None else rank)
    env["HVD_TPU_LOCAL_SIZE"] = str(local_size if local_size is not None else size)
    env["HVD_TPU_COORD"] = coord
    env["HVD_TPU_DATA"] = ",".join(data)
    if xla_coord:
        env["HVD_TPU_XLA_COORD"] = xla_coord
    # Ranks that compile share one persistent cache at a path that does not
    # move (common/compile_cache.py); a rank that never imports JAX ignores
    # the variable.
    from horovod_tpu.common.compile_cache import place_compile_cache

    place_compile_cache(env)
    # Sanitized engine builds (docs/contributing.md#sanitized-engine
    # -builds): the instrumented libhvdtpu.<mode>.so needs the sanitizer
    # runtime preloaded into the RANK processes — but preloading the
    # launcher's own python wedges it (TSan interceptors vs the rank
    # multiplexing), so hvdrun resolves and injects LD_PRELOAD here
    # instead of asking users to export it job-wide.  A pre-existing
    # LD_PRELOAD (jemalloc etc.) is composed with, sanitizer first —
    # skipping it would dlopen the instrumented engine without its
    # runtime and die in __tsan init.
    if env.get("HVD_TPU_SANITIZE"):
        from horovod_tpu.engine.build import sanitizer_preload

        preload = None  # None = bad mode (the rank's build() raises too)
        try:
            preload = sanitizer_preload(env["HVD_TPU_SANITIZE"].strip()
                                        .lower())
        except ValueError as exc:
            _warn_sanitize_once(str(exc))
        existing = env.get("LD_PRELOAD", "")
        if preload:
            if preload not in existing.split(":"):
                env["LD_PRELOAD"] = (f"{preload}:{existing}" if existing
                                     else preload)
        elif preload == "" and not any(
                runtime in existing
                for runtime in ("tsan", "asan", "ubsan")):
            # Fail loudly up front: without the runtime every rank would
            # dlopen the instrumented engine and die in __tsan/__asan
            # init with N identical cryptic errors.  (A user-supplied
            # LD_PRELOAD that already names a sanitizer runtime is the
            # one case resolution failure is fine.)
            _warn_sanitize_once(
                f"HVD_TPU_SANITIZE={env['HVD_TPU_SANITIZE']} is set but "
                f"the sanitizer runtime could not be resolved "
                f"(g++ -print-file-name); ranks will likely fail to load "
                f"the instrumented engine. Install the libsanitizer "
                f"runtime or set LD_PRELOAD yourself.")
    return env


# Launch-time sanitizer diagnostics already emitted (make_rank_env runs
# once PER RANK; the job needs each warning once).
_sanitize_warned: set = set()


def _warn_sanitize_once(msg: str) -> None:
    if msg not in _sanitize_warned:
        _sanitize_warned.add(msg)
        print(f"hvdrun: WARNING: {msg}", file=sys.stderr)


def allocate_endpoints(size: int, host: str = "127.0.0.1", extra: int = 0):
    """Coordinator + per-rank data endpoints, picked as ONE held batch
    (pick_free_ports) so no port is handed out twice within a launch.
    ``extra`` reserves additional ports in the same batch; they come
    back as a third element when requested."""
    from horovod_tpu.common.basics import pick_free_ports

    ports = pick_free_ports(size + 1 + extra, host)
    coord = f"{host}:{ports[0]}"
    data = [f"{host}:{p}" for p in ports[1:size + 1]]
    if extra:
        return coord, data, ports[size + 1:]
    return coord, data


def _kill_grace_sec() -> float:
    """How long a finished/failed job waits for its remaining ranks to
    exit on their own before SIGKILLing them (the engine cascades a
    coordinated shutdown/abort, so healthy ranks exit well within this).
    Tunable so fault-injection tests with deliberately wedged ranks stay
    fast; shared by the static and elastic launchers."""
    try:
        return float(os.environ.get("HVD_TPU_KILL_GRACE_SEC") or 15.0)
    except ValueError:
        return 15.0


class _StderrTee:
    """Echo one rank's stderr to the launcher's stderr line-by-line while
    retaining the last N lines.  Non-capture runs (the hvdrun CLI) keep
    their live streaming AND get a first-failing-rank tail in the failure
    report — without buffering whole-job output in memory."""

    def __init__(self, pipe, tail_lines: int = 80):
        import collections
        import threading

        self._pipe = pipe
        self._tail = collections.deque(maxlen=tail_lines)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        try:
            for line in self._pipe:
                sys.stderr.write(line)
                self._tail.append(line)
        except (ValueError, OSError):
            pass  # pipe torn down mid-read (kill cascade)
        finally:
            try:
                self._pipe.close()
            except OSError:
                pass

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)

    def text(self) -> str:
        return "".join(self._tail)


def run_command(cmd: Sequence[str], np: int,
                env: Optional[Dict[str, str]] = None,
                timeout: float = 300.0,
                capture: bool = False,
                host: str = "127.0.0.1",
                tpu_pin: bool = False,
                tpu_topology: Optional[str] = None) -> List[RankResult]:
    """Launch `cmd` as `np` local ranks; wait for all; kill all on any
    failure.  Returns per-rank results (stdout/stderr only if capture).
    ``tpu_pin`` confines each rank's libtpu client to the chip matching
    its local_rank (runner/tpu_pin.py)."""
    # One held batch for every port this launch needs — separate picks
    # can collide with each other once their probe sockets close.
    coord, data, spare = allocate_endpoints(
        np, host, extra=1 + (np if tpu_pin else 0))
    xla_coord = f"{host}:{spare[0]}"
    pin_envs = [{} for _ in range(np)]
    if tpu_pin:
        from horovod_tpu.runner.tpu_pin import pin_env

        addresses = [f"{host}:{p}" for p in spare[1:]]
        pin_envs = [pin_env(r, r, np, 0, 1, addresses, tpu_topology)
                    for r in range(np)]
    procs = []
    tees = []
    for r in range(np):
        rank_env = make_rank_env(r, np, coord, data, env,
                                 xla_coord=xla_coord)
        rank_env.update(pin_envs[r])
        p = subprocess.Popen(
            list(cmd),
            env=rank_env,
            stdout=subprocess.PIPE if capture else None,
            stderr=subprocess.PIPE,
            text=True, start_new_session=True)
        # Non-capture: tee stderr (live echo + retained tail for the
        # failure report).  Capture: communicate() drains it as before.
        tees.append(None if capture else _StderrTee(p.stderr))
        procs.append(p)
    try:
        return _wait_all(cmd, procs, timeout, tees)
    finally:
        # Typed aborts, injected crashes, timeouts, clean exits alike:
        # no attempt may strand a /dev/shm segment (see sweep docstring).
        sweep_shm_segments(coord)


def run_hosts(cmd: Sequence[str], np: int, hosts_spec: str,
              port_base: Optional[int] = None,
              env: Optional[Dict[str, str]] = None,
              timeout: float = 3e7,
              capture: bool = False,
              ssh_args: Sequence[str] = (),
              tpu_pin: bool = False,
              tpu_topology: Optional[str] = None) -> List[RankResult]:
    """Launch `cmd` across a host spec ("host1:2,host2:2"): local ranks
    spawn directly, remote ranks over ssh (the `mpirun -H` replacement,
    /root/reference/docs/running.md).  Keys of `env` that differ from this
    process's environment are forwarded to remote ranks too (inlined into
    the ssh command), so overrides like PYTHONPATH reach every rank."""
    from horovod_tpu.runner.hosts import DEFAULT_PORT_BASE, plan, ssh_command

    placements = plan(np, hosts_spec, port_base or DEFAULT_PORT_BASE,
                      tpu_pin=tpu_pin, tpu_topology=tpu_topology)
    base_env = dict(env if env is not None else os.environ)
    overrides = {k: v for k, v in base_env.items()
                 if os.environ.get(k) != v}
    # Remote ranks get a fresh login environment from ssh, not this
    # process's: forward the accelerator/runtime selection explicitly so
    # a remote rank resolves the same platform and imports as a local one
    # (mpirun inherited these wholesale; ssh does not).
    for key in ("JAX_PLATFORMS", "PYTHONPATH", "XLA_FLAGS",
                "HVD_TPU_XLA_DATA_PLANE", "HOROVOD_XLA_DATA_PLANE"):
        if key in base_env:
            overrides.setdefault(key, base_env[key])
    procs = []
    tees = []
    for p in placements:
        rank_env = dict(base_env)
        rank_env.update(p.env)
        argv = list(cmd) if p.is_local else ssh_command(
            p, cmd, ssh_args, extra_env=overrides)
        proc = subprocess.Popen(
            argv, env=rank_env,
            stdout=subprocess.PIPE if capture else None,
            stderr=subprocess.PIPE,
            text=True, start_new_session=True)
        tees.append(None if capture else _StderrTee(proc.stderr))
        procs.append(proc)
    try:
        return _wait_all(cmd, procs, timeout, tees)
    finally:
        # Local ranks' segments only; remote hosts clean their own via
        # the engine's unlink-on-death paths.
        sweep_shm_segments(placements[0].env.get("HVD_TPU_COORD", "")
                           if placements else "")


def _kill_rank(p) -> None:
    """Kill a rank and everything it spawned.  Ranks start in their own
    session (start_new_session=True), so killing the process group reaches
    grandchildren too — a rank that exec'd through a shell (the ssh path)
    would otherwise leave a descendant holding the stdout/stderr pipes,
    and communicate() below would block on them long past the timeout."""
    import signal

    try:
        os.killpg(p.pid, signal.SIGKILL)
    except OSError:
        p.kill()


def _wait_all(cmd: Sequence[str], procs, timeout: float,
              tees: Optional[List[Optional["_StderrTee"]]] = None
              ) -> List[RankResult]:
    import time

    # Poll all ranks; when one fails, give the rest a grace period (the
    # engine cascades a coordinated shutdown/abort to every rank) and then
    # kill stragglers -- the fail-fast the reference left to mpirun.  The
    # grace is tunable (HVD_TPU_KILL_GRACE_SEC) so fault-injection tests
    # with deliberately wedged ranks stay fast.
    grace_sec = _kill_grace_sec()
    # A rank exiting rc 0 while its peers keep running for MINUTES means
    # the job can never form or finish (synchronous SPMD completes in
    # lockstep): a rank that dies cleanly before init() completes — e.g.
    # during a --max-restarts relaunch window — would otherwise park the
    # remaining ranks in their connect retries until the TOTAL --timeout
    # budget (often unbounded) burned, with no failure report.  Kill the
    # stragglers after a bounded completion grace instead, so the attempt
    # fails fast, counts against --max-restarts, and carries the stderr
    # tail.  The default is deliberately generous — legitimate post-
    # barrier work (rank 0 writing a large final checkpoint after the
    # workers exited) must fit inside it; <= 0 disables the deadline.
    try:
        straggler_sec = float(
            os.environ.get("HVD_TPU_EXIT_STRAGGLER_SEC") or 300.0)
    except ValueError:
        straggler_sec = 300.0
    deadline = time.monotonic() + timeout
    grace_deadline = None
    zero_exit_deadline = None
    first_failed = None  # rank index of the first observed nonzero exit
    timed_out = False
    try:
        # Poll EVERY rank each pass (a short-circuiting any(p.poll()...)
        # would stop at the first live rank and never populate the
        # returncodes the deadline scans below read).
        while sum(1 for p in procs if p.poll() is None):
            now = time.monotonic()
            if grace_deadline is None:
                failed = [i for i, p in enumerate(procs)
                          if p.returncode not in (None, 0)]
                if failed:
                    first_failed = failed[0]
                    grace_deadline = now + grace_sec
            if (straggler_sec > 0 and zero_exit_deadline is None
                    and any(p.returncode == 0 for p in procs)):
                zero_exit_deadline = now + straggler_sec
            if (now >= deadline or (grace_deadline and now >= grace_deadline)
                    or (zero_exit_deadline and now >= zero_exit_deadline)):
                timed_out = now >= deadline
                for p in procs:
                    if p.poll() is None:
                        _kill_rank(p)
                break
            time.sleep(0.05)
    except BaseException:
        # Ctrl-C / SIGTERM on the launcher: ranks run in their own
        # sessions (no terminal signal fan-out), so propagate the kill
        # to every rank group before re-raising.
        for p in procs:
            if p.poll() is None:
                _kill_rank(p)
        raise
    results = _collect_results(procs, tees, first_failed=first_failed)
    if timed_out:
        raise subprocess.TimeoutExpired(cmd, timeout)
    return results


def _collect_results(procs, tees,
                     first_failed: Optional[int] = None) -> List[RankResult]:
    """Drain every launched process into a :class:`RankResult` after the
    polling loop decided the job is over: bounded waits, group-kill of
    anything (or any orphan sharing its pipes) that survives them, and
    stdout/stderr salvage — shared by ``_wait_all`` and
    ``run_membership``."""
    results = []
    for r, p in enumerate(procs):
        tee = tees[r] if tees else None
        if tee is not None:
            # Tee'd stderr is drained by its thread; only wait for the
            # process (communicate() would race the reader on the pipe).
            try:
                p.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                _kill_rank(p)
                try:
                    p.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    pass
            tee.join(timeout=5.0)
            out, errout = "", tee.text()
        else:
            try:
                out, errout = p.communicate(timeout=30.0)
            except subprocess.TimeoutExpired:
                # A straggler (or an orphan sharing its pipes) survived:
                # kill its group and salvage what it wrote; never hang the
                # launcher.
                _kill_rank(p)
                try:
                    out, errout = p.communicate(timeout=5.0)
                except subprocess.TimeoutExpired:
                    out, errout = "", ""
        rc = p.returncode if p.returncode is not None else -9
        results.append(RankResult(r, rc, out or "", errout or "",
                                  first_failure=(r == first_failed)))
    return results


def _elastic_bounds(np: int, min_np: Optional[int],
                    max_np: Optional[int]) -> Tuple[int, int]:
    """Normalize and validate the elastic membership bounds — the ONE
    place the rules live (run_elastic, run_membership, and the CLI all
    route through it).  An unset --min-np means "all launched ranks must
    finish", NOT "one survivor is enough"; an unset --max-np means no
    planned growth."""
    # `is not None`, not truthiness: an explicit --min-np 0 must reach the
    # range check and be rejected, not silently read as "unset".
    min_np = min_np if min_np is not None else np
    max_np = max_np if max_np is not None else np
    if not (1 <= min_np <= np <= max_np):
        raise ValueError(
            f"need 1 <= min-np ({min_np}) <= np ({np}) <= max-np ({max_np})")
    return min_np, max_np


def _check_elastic_support(hosts_spec: Optional[str],
                           tpu_pin: bool) -> None:
    """Reject launcher features elastic membership cannot compose with
    yet, loudly, instead of silently dropping them."""
    if hosts_spec:
        raise ValueError(
            "elastic membership (min_np/max_np) supports single-host "
            "launches only")
    if tpu_pin:
        raise ValueError(
            "elastic membership (min_np/max_np) does not support TPU "
            "chip pinning yet: standby ranks have no stable local_rank "
            "to pin to")


def run_elastic(cmd: Sequence[str], np: int, max_restarts: int = 0,
                env: Optional[Dict[str, str]] = None,
                timeout: float = 300.0,
                capture: bool = False,
                host: str = "127.0.0.1",
                hosts_spec: Optional[str] = None,
                port_base: Optional[int] = None,
                tpu_pin: bool = False,
                tpu_topology: Optional[str] = None,
                min_np: Optional[int] = None,
                max_np: Optional[int] = None,
                max_rejoins: Optional[int] = None,
                report: Callable[[str], None] = None):
    """Job-level restart (docs/fault-tolerance.md): launch the job, and on
    failure — any rank exiting nonzero, or the job timing out — group-kill
    the survivors (``_wait_all`` already does) and relaunch ALL ranks with
    ``HVD_TPU_RESTART_EPOCH`` incremented, up to ``max_restarts`` times.
    Fresh endpoints are allocated per attempt, so a crashed job's
    lingering sockets cannot poison the relaunch.  Returns
    ``(results, restarts_used)``; the caller's training script is expected
    to resume from its latest checkpoint (see
    ``horovod_tpu.jax.train.load_latest_checkpoint`` / the keras
    ``BroadcastGlobalVariablesCallback`` glue).

    With ``min_np``/``max_np`` set (``hvdrun --min-np/--max-np``), each
    attempt runs under the elastic membership launcher
    (:func:`run_membership`): rank deaths shrink the job in place and
    standbys rejoin, with NO relaunch as long as at least ``min_np``
    members survive.  Only when elastic continuation fails — the
    coordinator died, or survivors fell below ``min_np`` — does the
    attempt count as a failure and the full relaunch + checkpoint-resume
    fallback above kick in."""
    import time

    if report is None:
        def report(msg):
            print(msg, file=sys.stderr, flush=True)
    elastic = min_np is not None or max_np is not None
    if elastic:
        # Normalize the bounds HERE so the success verdict below uses the
        # same floor run_membership enforces (an unset --min-np means "all
        # launched ranks must finish", NOT "one survivor is enough").
        min_np, max_np = _elastic_bounds(np, min_np, max_np)
        _check_elastic_support(hosts_spec, tpu_pin)
    base_env = dict(env if env is not None else os.environ)
    results: List[RankResult] = []
    # `timeout` is the TOTAL wall-clock budget across every attempt (the
    # --timeout contract: "kill the job after this many seconds"), not a
    # per-attempt allowance that restarts would multiply.
    deadline = time.monotonic() + timeout
    for epoch in range(max_restarts + 1):
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise subprocess.TimeoutExpired(list(cmd), timeout)
        run_env = dict(base_env)
        run_env["HVD_TPU_RESTART_EPOCH"] = str(epoch)
        try:
            if elastic:
                results = run_membership(cmd, np, min_np=min_np,
                                         max_np=max_np, env=run_env,
                                         timeout=remaining,
                                         capture=capture, host=host,
                                         max_rejoins=max_rejoins,
                                         report=report)
            elif hosts_spec:
                results = run_hosts(cmd, np, hosts_spec,
                                    port_base=port_base, env=run_env,
                                    timeout=remaining, capture=capture,
                                    tpu_pin=tpu_pin,
                                    tpu_topology=tpu_topology)
            else:
                results = run_command(cmd, np, env=run_env,
                                      timeout=remaining,
                                      capture=capture, host=host,
                                      tpu_pin=tpu_pin,
                                      tpu_topology=tpu_topology)
        except subprocess.TimeoutExpired:
            if epoch == max_restarts:
                raise
            report(f"hvdrun: job timed out (restart epoch {epoch}); "
                   f"restarting ({epoch + 1}/{max_restarts})")
            continue
        ok = (membership_succeeded(results, min_np) if elastic
              else all(r.returncode == 0 for r in results))
        if ok:
            return results, epoch
        if epoch < max_restarts:
            rpt = failure_report(results)
            report(f"hvdrun: job failed (restart epoch {epoch}):"
                   + (f"\n{rpt}" if rpt else "")
                   + f"\nhvdrun: restarting ({epoch + 1}/{max_restarts})")
    return results, max_restarts


def run_membership(cmd: Sequence[str], np: int,
                   min_np: Optional[int] = None,
                   max_np: Optional[int] = None,
                   env: Optional[Dict[str, str]] = None,
                   timeout: float = 300.0,
                   capture: bool = False,
                   host: str = "127.0.0.1",
                   rejoin_delay: float = 1.0,
                   max_rejoins: Optional[int] = None,
                   report: Callable[[str], None] = None) -> List[RankResult]:
    """Elastic membership launcher (``hvdrun --min-np/--max-np``,
    docs/fault-tolerance.md#elastic-membership).

    Launches ``np`` ranks with ``HVD_TPU_ELASTIC=1``.  Unlike
    :func:`run_command`, a dying rank does NOT trigger the kill cascade:
    the engine reshapes the job around the survivors, so the launcher
    keeps the job alive while at least ``min_np`` ranks (the coordinator
    included) are still running, and — while membership is below
    ``max_np`` — spawns standby replacements (``HVD_TPU_REJOIN=1``, a
    fresh data endpoint) that register with the live coordinator and are
    admitted at the next reshape barrier.

    Fatal cases kill everything and return failing results so an outer
    ``run_elastic(..., max_restarts=N)`` can fall back to the
    full-relaunch + checkpoint-resume path: the coordinator (launch rank
    0) dying, or the running count dropping below ``min_np``.

    Returns one :class:`RankResult` per process ever launched — the
    initial ranks keep their launch indices, standbys are numbered from
    ``np`` up.
    """
    import time

    if report is None:
        def report(msg):
            print(msg, file=sys.stderr, flush=True)
    min_np, max_np = _elastic_bounds(np, min_np, max_np)
    if max_rejoins is None:
        # Budget both the planned growth toward max_np (launching below
        # it is legitimate: -np 2 --max-np 6 starts small and grows) and
        # crash replacements, so initial backfill cannot exhaust the
        # budget real failures need later.
        max_rejoins = 2 * max_np
    coord, data = allocate_endpoints(np, host)
    base_env = dict(env if env is not None else os.environ)
    base_env["HVD_TPU_ELASTIC"] = "1"
    base_env["HVD_TPU_MIN_NP"] = str(min_np)

    procs: List = []
    tees: List = []

    def spawn(rank_env):
        p = subprocess.Popen(
            list(cmd), env=rank_env,
            stdout=subprocess.PIPE if capture else None,
            stderr=subprocess.PIPE,
            text=True, start_new_session=True)
        procs.append(p)
        tees.append(None if capture else _StderrTee(p.stderr))
        return p

    for r in range(np):
        spawn(make_rank_env(r, np, coord, data, base_env))

    grace_sec = _kill_grace_sec()
    deadline = time.monotonic() + timeout
    completion_deadline = None  # armed when the first rank finishes rc 0
    rejoin_at = None            # next standby spawn time
    rejoins_used = 0
    fatal = False
    reported_dead: set = set()
    first_dead = None  # slot of the CHRONOLOGICALLY first death observed
    try:
        while any(p.poll() is None for p in procs):
            now = time.monotonic()
            running = sum(1 for p in procs if p.poll() is None)
            completed = sum(1 for p in procs if p.returncode == 0)
            for i, p in enumerate(procs):
                if p.returncode not in (None, 0) and i not in reported_dead:
                    if first_dead is None:
                        first_dead = i
                    reported_dead.add(i)
                    # 1-based to match the "spawning standby N" line.
                    label = (f"rank {i}" if i < np
                             else f"standby {i - np + 1} (slot {i})")
                    report(f"hvdrun: {label} exited with "
                           f"{signal_name(p.returncode)}; "
                           f"{running} member(s) still running "
                           f"(elastic min-np {min_np})")
            if procs[0].poll() is not None and procs[0].returncode != 0:
                # The coordinator owns membership; without it nothing can
                # reshape.  Fall back to the outer restart path.
                report("hvdrun: coordinator (rank 0) died; elastic "
                       "continuation impossible")
                fatal = True
                break
            if completed:
                # Synchronous SPMD finishes in lockstep: once one member
                # completed, the rest (admitted standbys included) should
                # follow within the grace.  Stragglers past it are wedged.
                if completion_deadline is None:
                    completion_deadline = now + max(grace_sec, 5.0)
                if now >= completion_deadline:
                    # Wedged stragglers — and standbys still waiting for
                    # an admission that will never come — get killed, not
                    # waited out.
                    for p in procs:
                        if p.poll() is None:
                            _kill_rank(p)
                    break
            elif running < min_np:
                report(f"hvdrun: only {running} member(s) running "
                       f"(< min-np {min_np}); giving up on elastic "
                       f"continuation")
                fatal = True
                break
            elif running < max_np and rejoins_used < max_rejoins:
                # Backfill toward max-np with standbys.  The delay keeps a
                # crash-looping command from hot-spawning; each standby
                # gets a fresh endpoint so a dead rank's lingering socket
                # cannot poison the rejoin.
                if rejoin_at is None:
                    rejoin_at = now + rejoin_delay
                elif now >= rejoin_at:
                    rejoin_at = None
                    rejoins_used += 1
                    ep = f"{host}:{pick_free_port(host)}"
                    standby_env = dict(base_env)
                    standby_env.update({
                        "HVD_TPU_REJOIN": "1",
                        "HVD_TPU_RANK": "0", "HVD_TPU_SIZE": "1",
                        "HVD_TPU_LOCAL_RANK": "0", "HVD_TPU_LOCAL_SIZE": "1",
                        "HVD_TPU_COORD": coord, "HVD_TPU_DATA": ep,
                    })
                    report(f"hvdrun: spawning standby {rejoins_used} at {ep} "
                           f"({running}/{max_np} members running)")
                    spawn(standby_env)
            else:
                rejoin_at = None
            if now >= deadline:
                for p in procs:
                    if p.poll() is None:
                        _kill_rank(p)
                raise subprocess.TimeoutExpired(cmd, timeout)
            time.sleep(0.05)
    except BaseException:
        for p in procs:
            if p.poll() is None:
                _kill_rank(p)
        sweep_shm_segments(coord)
        raise
    if fatal:
        for p in procs:
            if p.poll() is None:
                _kill_rank(p)
    results = _collect_results(procs, tees)
    sweep_shm_segments(coord)
    # Flag the CHRONOLOGICALLY first death for the failure report — the
    # lowest-index nonzero exit is often the launcher's own fatal-path
    # kill cascade, not the root cause.  (Success itself is judged by
    # membership_succeeded: coordinator clean + >= min_np clean.)
    if first_dead is not None:
        results[first_dead].first_failure = True
    else:
        for r in results:
            if r.returncode != 0:
                r.first_failure = True
                break
    return results


def membership_succeeded(results: List[RankResult],
                         min_np: int) -> bool:
    """Whether an elastic run (``run_membership``) counts as success:
    the coordinator (slot 0) exited 0 and at least ``min_np`` members
    completed cleanly (deaths the job reshaped around do not fail it)."""
    if not results or results[0].returncode != 0:
        return False
    return sum(1 for r in results if r.returncode == 0) >= min_np


_FN_RUNNER = """\
import pickle, sys
with open(sys.argv[1], 'rb') as f:
    fn = pickle.load(f)
fn()
"""


def launch_fn(fn: Callable[[], None], np: int,
              env: Optional[Dict[str, str]] = None,
              timeout: float = 300.0) -> List[RankResult]:
    """Run a picklable zero-arg callable on every rank (test convenience)."""
    import pickle
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".pkl", delete=False) as f:
        pickle.dump(fn, f)
        pkl = f.name
    with tempfile.NamedTemporaryFile(
            mode="w", suffix=".py", delete=False) as f:
        f.write(_FN_RUNNER)
        runner = f.name
    try:
        return run_command([sys.executable, runner, pkl], np, env=env,
                           timeout=timeout, capture=True)
    finally:
        os.unlink(pkl)
        os.unlink(runner)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu training job (mpirun replacement).")
    parser.add_argument("-np", "--num-proc", type=int, required=True,
                        help="number of ranks to launch")
    parser.add_argument("-H", "--hosts", default=None,
                        help="host spec 'host1:slots,host2:slots' — ranks "
                             "fill hosts in contiguous blocks; remote hosts "
                             "are reached over ssh (the mpirun -H "
                             "replacement). Default: all ranks local.")
    parser.add_argument("--port-base", type=int, default=None,
                        help="with -H: coordinator port (data ports follow)")
    parser.add_argument("--host", default="127.0.0.1",
                        help="bind address for coordinator/data endpoints "
                             "(single-host mode)")
    parser.add_argument("--timeout", type=float, default=0.0,
                        help="kill the job after this many seconds (0 = none)")
    parser.add_argument("--timeline", default=None, metavar="DIR",
                        help="write one Chrome-trace file per rank under "
                             "DIR (rank0.json, rank1.json, ...; sets "
                             "HVD_TPU_TIMELINE=DIR).  Merge them with "
                             "tools/timeline_merge.py — see "
                             "docs/timeline.md")
    parser.add_argument("--postmortem-dir", default=None, metavar="DIR",
                        help="postmortem plane (docs/troubleshooting.md"
                             "#reading-a-postmortem): every rank writes a "
                             "rank-<N>.json crash/hang dump under DIR on "
                             "typed aborts, injected crashes, and fatal "
                             "exceptions (sets HVD_TPU_POSTMORTEM_DIR); "
                             "the failure report points at the first-"
                             "failing rank's dump.  Render with "
                             "tools/postmortem_dump.py DIR")
    parser.add_argument("--state-dir", default=None, metavar="DIR",
                        help="state plane (docs/fault-tolerance.md"
                             "#state-plane): spill each rank's async "
                             "shard snapshots under DIR (sets "
                             "HVD_TPU_STATE_DIR for every rank and "
                             "every --max-restarts relaunch); scripts "
                             "arm with hvd.state.arm().  Pair with "
                             "HVD_TPU_CKPT_KEEP to bound sharded-"
                             "checkpoint retention")
    parser.add_argument("--min-np", type=int, default=None,
                        help="elastic membership "
                             "(docs/fault-tolerance.md#elastic-membership): "
                             "keep the job alive while at least this many "
                             "ranks survive — a dying rank shrinks the job "
                             "in place (survivors re-negotiate size/rank "
                             "and resync by root broadcast, no relaunch or "
                             "checkpoint reload); below min-np the "
                             "--max-restarts checkpoint fallback fires")
    parser.add_argument("--max-np", type=int, default=None,
                        help="with --min-np: while membership is below "
                             "this, spawn standby ranks that rejoin the "
                             "live job at the next reshape barrier "
                             "(default: -np)")
    parser.add_argument("--serve", action="store_true",
                        help="serving mode (docs/inference.md): the "
                             "command defaults to the serving entrypoint "
                             "(python -m horovod_tpu.serving); rank 0 "
                             "opens the HTTP front door on "
                             "HVD_TPU_SERVE_PORT / --serve-port.  With "
                             "--min-np the job shrinks around dead ranks "
                             "and keeps serving (standby rejoin is "
                             "disabled: a fresh rank cannot recover the "
                             "in-flight KV state)")
    parser.add_argument("--serve-port", type=int, default=None,
                        help="with --serve: the front-door port (sets "
                             "HVD_TPU_SERVE_PORT)")
    parser.add_argument("--net-fault-spec", default=None, metavar="SPEC",
                        help="network chaos harness (docs/fault-tolerance"
                             ".md#failure-detection): deterministic link-"
                             "fault injection for every rank (sets "
                             "HVD_TPU_NET_FAULT_SPEC), e.g. "
                             "'link=0-1:drop@after=2', "
                             "'partition=0,1/2,3@after=1', "
                             "'link=1-2:delay=5|jitter=3', "
                             "'link=0-3:flaky=0.05'; composes with "
                             "HVD_TPU_FAULT_SPEC process faults")
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="on job failure (a rank died, or the engine "
                             "aborted on a dead/stalled rank), kill the "
                             "survivors and relaunch all ranks up to N "
                             "times with HVD_TPU_RESTART_EPOCH "
                             "incremented; training scripts resume from "
                             "their latest checkpoint (see "
                             "docs/fault-tolerance.md)")
    parser.add_argument("--tpu-pin", action="store_true",
                        default=None,
                        help="pin one TPU chip per rank by local_rank "
                             "(TPU_VISIBLE_CHIPS / TPU_PROCESS_BOUNDS; the "
                             "reference recipe's visible_device_list step). "
                             "Also enabled by HVD_TPU_PIN=1.")
    parser.add_argument("--tpu-topology", default=None,
                        help="per-host chip grid 'x,y[,z]' when it differs "
                             "from the built-in table (1/2/4/8 chips)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="training command, e.g. python train.py")
    args = parser.parse_args(argv)
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        if not args.serve:
            parser.error("no command given")
        cmd = [sys.executable, "-m", "horovod_tpu.serving"]
    if args.serve_port is not None and not args.serve:
        parser.error("--serve-port requires --serve")
    from horovod_tpu.runner.tpu_pin import pinning_requested

    tpu_pin = pinning_requested(args.tpu_pin)
    env = None
    if args.serve_port is not None:
        env = dict(os.environ)
        env["HVD_TPU_SERVE_PORT"] = str(args.serve_port)
    if args.state_dir:
        os.makedirs(args.state_dir, exist_ok=True)
        env = dict(env if env is not None else os.environ)
        env["HVD_TPU_STATE_DIR"] = args.state_dir
    if args.net_fault_spec is not None:
        env = dict(env if env is not None else os.environ)
        env["HVD_TPU_NET_FAULT_SPEC"] = args.net_fault_spec
    if args.postmortem_dir:
        os.makedirs(args.postmortem_dir, exist_ok=True)
        env = dict(env if env is not None else os.environ)
        env["HVD_TPU_POSTMORTEM_DIR"] = args.postmortem_dir
        # The launcher's own failure_report reads the env default too.
        os.environ["HVD_TPU_POSTMORTEM_DIR"] = args.postmortem_dir
    if args.timeline:
        os.makedirs(args.timeline, exist_ok=True)
        env = dict(env if env is not None else os.environ)
        # Trailing separator forces the directory form on EVERY rank —
        # remote (ssh) hosts don't share the launcher's filesystem, so a
        # bare path that only exists locally would fall back to the
        # legacy single-file mode there; ranks mkdir the trailing-sep
        # form themselves.
        env["HVD_TPU_TIMELINE"] = args.timeline.rstrip(os.sep) + os.sep
    elastic = args.min_np is not None or args.max_np is not None
    if elastic:
        try:
            _elastic_bounds(args.num_proc, args.min_np, args.max_np)
            _check_elastic_support(args.hosts, tpu_pin)
        except ValueError as e:
            parser.error(str(e))
    try:
        results, restarts = run_elastic(
            cmd, args.num_proc, max_restarts=args.max_restarts,
            env=env, timeout=args.timeout or 3e7, host=args.host,
            hosts_spec=args.hosts, port_base=args.port_base,
            tpu_pin=tpu_pin, tpu_topology=args.tpu_topology,
            min_np=args.min_np, max_np=args.max_np,
            # Serving is shrink-only: an admitted standby would join with
            # empty KV pages and silently corrupt every sequence it
            # touches, so elastic serve jobs never spawn standbys.
            max_rejoins=0 if args.serve else None)
    except subprocess.TimeoutExpired:
        print("hvdrun: job timed out", file=sys.stderr)
        return 124
    # Unset --min-np with --max-np means "may grow, must not shrink": the
    # success floor is the full launch size, not one survivor.
    ok = (membership_succeeded(
        results,
        args.min_np if args.min_np is not None else args.num_proc)
          if elastic else all(r.returncode == 0 for r in results))
    if restarts and ok:
        print(f"hvdrun: job succeeded after {restarts} restart(s)",
              file=sys.stderr)
    if elastic and ok:
        # Initial ranks only: a standby the launcher itself reaped at the
        # completion deadline (spawned but never admitted before the job
        # finished) was never a member, so it is not "lost".
        lost = sum(1 for r in results
                   if r.returncode != 0 and r.rank < args.num_proc)
        if lost:
            print(f"hvdrun: job completed elastically ({lost} member(s) "
                  f"lost and reshaped around)", file=sys.stderr)
        return 0
    rc = 0
    report = failure_report(results)
    if report:
        print(f"hvdrun: {report}", file=sys.stderr)
    for r in results:
        if r.returncode != 0 and rc == 0:
            # Signal deaths have negative returncodes; report 128+sig
            # like a shell would so the job never masks as success.
            rc = r.returncode if r.returncode > 0 else 128 - r.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
