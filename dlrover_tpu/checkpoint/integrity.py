"""Checkpoint integrity: per-file digests, step manifests, quarantine.

The trust chain (docs/CHECKPOINT.md):

* every shard writer digests the bytes it *meant* to write and records
  them in its ``.done`` file;
* node-0's ``commit_checkpoint`` assembles those records into a step
  ``MANIFEST.json``, re-reads every shard from storage, and only flips
  the tracker when the bytes on disk match the digests — a torn or
  bit-rotted write can never become the committed checkpoint;
* restore walks the ladder (shm → tracker step → newest fully-verified
  step), quarantining corrupt steps as ``checkpoint-<N>.corrupt`` so a
  bad step is never silently retried;
* ranks agree on ONE restore step via the master (``negotiate`` below),
  so partial corruption cannot split-brain the world.

Digests default to crc32 (zlib — fast enough for GB-scale shards on the
commit path); set ``DLROVER_CKPT_DIGEST=sha256`` for cryptographic
strength on storage you do not trust.
"""

import dataclasses
import hashlib
import json
import os
import time
import zlib
from typing import Any, Dict, List, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.checkpoint.storage import (
    CheckpointStorage,
    STEP_DIR_PREFIX,
    durable_write,
    read_tracker,
    step_dir,
)

# Lives INSIDE the step dir so quarantine/deletion move it with the data.
MANIFEST_FILE = "MANIFEST.json"
QUARANTINE_SUFFIX = ".corrupt"
_DIGEST_ENV = "DLROVER_CKPT_DIGEST"


def digest_alg() -> str:
    alg = os.environ.get(_DIGEST_ENV, "crc32").strip().lower()
    return alg if alg in ("crc32", "sha256") else "crc32"


def compute_digest(blob: bytes, alg: Optional[str] = None) -> str:
    alg = alg or digest_alg()
    if alg == "sha256":
        return hashlib.sha256(blob).hexdigest()
    return format(zlib.crc32(blob) & 0xFFFFFFFF, "08x")


def file_record(name: str, blob: bytes) -> Dict[str, Any]:
    """Manifest entry for one file, digesting the INTENDED bytes."""
    alg = digest_alg()
    return {
        "file": name,
        "alg": alg,
        "digest": compute_digest(blob, alg),
        "size": len(blob),
    }


@dataclasses.dataclass
class VerifyResult:
    """Outcome of verifying one step directory."""

    step: int
    status: str  # "ok" | "legacy" | "corrupt" | "missing"
    reason: str = ""
    files: int = 0

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def usable(self) -> bool:
        # "legacy" = pre-manifest checkpoint: unverifiable but not known
        # bad; still restorable so an upgrade never strands old saves.
        return self.status in ("ok", "legacy")


def manifest_path(root: str, step: int) -> str:
    return os.path.join(step_dir(root, step), MANIFEST_FILE)


def write_manifest(
    storage: CheckpointStorage,
    root: str,
    step: int,
    records: List[Dict[str, Any]],
) -> Dict[str, Any]:
    manifest = {
        "step": step,
        "alg": digest_alg(),
        "created": time.time(),
        "files": sorted(records, key=lambda r: r.get("file", "")),
    }
    durable_write(
        storage, json.dumps(manifest, indent=1), manifest_path(root, step)
    )
    return manifest


def read_manifest(
    storage: CheckpointStorage, root: str, step: int
) -> Optional[Dict[str, Any]]:
    blob = storage.read(manifest_path(root, step))
    if blob is None:
        return None
    try:
        manifest = json.loads(blob)
        if not isinstance(manifest, dict) or "files" not in manifest:
            return {}
        return manifest
    except (ValueError, UnicodeDecodeError):
        return {}  # present but unreadable: corrupt, not legacy


def verify_step(
    storage: CheckpointStorage,
    root: str,
    step: int,
    deep: bool = True,
) -> VerifyResult:
    """Check one step dir against its manifest.

    ``deep=False`` only checks the manifest's files exist (cheap guard
    for retention decisions); ``deep=True`` re-reads every file and
    compares digests (the commit / restore-ladder check).
    """
    sdir = step_dir(root, step)
    if not storage.exists(sdir):
        return VerifyResult(step, "missing", "step dir does not exist")
    manifest = read_manifest(storage, root, step)
    if manifest is None:
        return VerifyResult(
            step, "legacy", "no manifest (pre-integrity checkpoint)"
        )
    if not manifest:
        return VerifyResult(step, "corrupt", "manifest unreadable")
    entries = manifest.get("files") or []
    for rec in entries:
        fname = rec.get("file", "")
        fpath = os.path.join(sdir, fname)
        if not deep:
            if not storage.exists(fpath):
                return VerifyResult(
                    step, "corrupt", f"missing file {fname}", len(entries)
                )
            continue
        blob = storage.read(fpath)
        if blob is None:
            return VerifyResult(
                step, "corrupt", f"missing file {fname}", len(entries)
            )
        if "size" in rec and len(blob) != int(rec["size"]):
            return VerifyResult(
                step,
                "corrupt",
                f"{fname}: size {len(blob)} != manifest {rec['size']}",
                len(entries),
            )
        if "digest" in rec:
            got = compute_digest(blob, rec.get("alg"))
            if got != rec["digest"]:
                return VerifyResult(
                    step,
                    "corrupt",
                    f"{fname}: digest {got} != manifest {rec['digest']}",
                    len(entries),
                )
    _metric("dlrover_ckpt_verify_total").inc(
        result="ok" if entries else "empty"
    )
    return VerifyResult(step, "ok", files=len(entries))


def quarantine_step(
    storage: CheckpointStorage,
    root: str,
    step: int,
    reason: str,
) -> bool:
    """Rename ``checkpoint-<step>`` → ``checkpoint-<step>.corrupt`` so the
    bad bytes are kept for forensics but never restored again.  Emits the
    durable telemetry verdict + Prometheus counter.  Concurrent ranks may
    race the rename on shared storage — whoever loses just observes the
    source gone, which counts as quarantined."""
    src = step_dir(root, step)
    dst = src + QUARANTINE_SUFFIX
    moved = False
    try:
        if storage.exists(src):
            if storage.exists(dst):
                # A previous incarnation already quarantined this step and
                # a retry re-created the dir: drop the newer bad copy.
                storage.remove(src)
            else:
                moved = storage.move(src, dst)
        else:
            moved = storage.exists(dst)
    except OSError:
        logger.warning("could not quarantine step %s", step, exc_info=True)
    _metric("dlrover_ckpt_verify_total").inc(result="corrupt")
    _metric("dlrover_ckpt_quarantine_total").inc()
    try:
        from dlrover_tpu.telemetry import events as tevents

        tevents.emit(
            "verdict",
            action="ckpt_quarantine",
            step=step,
            reason=reason,
            quarantined=bool(moved),
        )
    except Exception:  # noqa: BLE001 — telemetry must not break restore
        pass
    logger.error(
        "checkpoint step %s QUARANTINED (%s): %s", step, reason,
        dst if moved else "rename failed; step left in place",
    )
    return moved


def list_quarantined(storage: CheckpointStorage, root: str) -> List[str]:
    return [
        e
        for e in storage.listdir(root)
        if str(e).startswith(STEP_DIR_PREFIX)
        and str(e).endswith(QUARANTINE_SUFFIX)
    ]


def ladder_candidates(
    storage: CheckpointStorage, root: str
) -> List[int]:
    """Restore-ladder order: newest step first.  Steps NEWER than the
    tracker are included — a fully verified manifest above the tracker
    means every shard landed and only the tracker flip was lost
    (``ckpt_stale_tracker``); the per-step verification in the ladder
    decides whether they are actually usable (a manifest-less dir above
    the tracker is in-flight and gets skipped).  Newest-first must match
    :func:`locally_verified_steps` — if the solo ladder and the
    consensus ranked the same disk differently, a world restoring with
    and without a master would time-travel to different steps."""
    from dlrover_tpu.checkpoint.deletion import list_step_dirs

    return sorted(list_step_dirs(storage, root), reverse=True)


def locally_verified_steps(
    storage: CheckpointStorage,
    root: str,
    deep: bool = True,
    quarantine: bool = False,
) -> List[int]:
    """Steps this node could restore from, newest first (the consensus
    report).  Corrupt steps are skipped (optionally quarantined); steps
    newer than the tracker need a verified manifest (an in-flight save
    without one is skipped silently — it may still be mid-write)."""
    tracker = read_tracker(storage, root)
    out: List[int] = []
    for step in ladder_candidates(storage, root):
        res = verify_step(storage, root, step, deep=deep)
        if res.ok:
            out.append(step)
        elif res.status == "legacy":
            if tracker is not None and step <= tracker:
                out.append(step)
        elif res.status == "corrupt":
            if quarantine:
                quarantine_step(storage, root, step, res.reason)
    return sorted(out, reverse=True)


def negotiate(
    client,
    node_rank: int,
    steps: List[int],
    world_size: int,
    round_id: int = 0,
    timeout: float = 60.0,
    poll: float = 0.5,
) -> Optional[int]:
    """Agree on ONE restore step across the world via the master.

    Reports this rank's locally-verifiable steps, then polls until every
    rank reported; the master returns the highest step verifiable
    everywhere.  Returns None when no common step exists (cold start) or
    the master never converged within ``timeout`` (callers fall back to
    the local ladder — degraded but not wedged)."""
    try:
        client.report_restorable_steps(
            node_rank=node_rank, steps=list(steps), round_id=round_id
        )
    except Exception:  # noqa: BLE001 — master gone: local ladder fallback
        logger.warning("restore consensus: report failed", exc_info=True)
        return None
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            decision = client.get_restore_decision(
                round_id=round_id, world_size=world_size
            )
        except Exception:  # noqa: BLE001
            logger.warning("restore consensus: poll failed", exc_info=True)
            return None
        if decision.ready:
            step = decision.step if decision.step >= 0 else None
            logger.info(
                "restore consensus (round %s): %s ranks agreed on step %s",
                round_id, decision.reported, step,
            )
            return step
        time.sleep(poll)
    logger.warning(
        "restore consensus timed out after %.0fs (round %s); falling "
        "back to the local restore ladder", timeout, round_id,
    )
    return None


def _metric(name: str):
    from dlrover_tpu.telemetry import metrics

    helps = {
        "dlrover_ckpt_verify_total": (
            "Checkpoint step verifications by result."
        ),
        "dlrover_ckpt_quarantine_total": (
            "Checkpoint steps quarantined as *.corrupt."
        ),
        "dlrover_ckpt_restore_fallback_total": (
            "Restores that fell back past the newest step."
        ),
        "dlrover_ckpt_scrub_runs_total": (
            "Background scrubber validation sweeps."
        ),
        "dlrover_ckpt_restore_leaves_total": (
            "Restored array leaves by path: uploaded as saved (direct) "
            "or pasted together on the host first (assembled)."
        ),
    }
    return metrics.counter(name, helps.get(name, ""))
