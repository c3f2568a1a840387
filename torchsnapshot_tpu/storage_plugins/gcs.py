"""Google Cloud Storage plugin — the TPU-adjacent object store.

Reference parity: torchsnapshot/storage_plugins/gcs.py:47-211 (resumable
uploads, chunked/ranged downloads, transient-error taxonomy, shared
collective-progress retry). Blocking ``google-resumable-media`` calls are
bridged to asyncio on a dedicated thread pool, sized to the per-rank I/O
concurrency knob so storage writes overlap.

Auth: application-default credentials (the standard on TPU VMs, whose
metadata server grants the attached service account). Bucket paths are
``gs://bucket/prefix`` URLs.
"""

from __future__ import annotations

import asyncio
import io
import logging
import os
import random
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional, Tuple

from .. import knobs, telemetry
from ..io_types import ReadIO, StoragePlugin, WriteIO
from ..telemetry import names as metric_names
from ..telemetry import observe_io
from ..telemetry.trace import get_recorder as _trace_recorder, io_span
from ..utils.tracing import run_in_executor, trace_annotation
from .retry import CollectiveProgressRetryStrategy

logger = logging.getLogger(__name__)

_UPLOAD_CHUNK_SIZE = 100 * 1024 * 1024
_DOWNLOAD_CHUNK_SIZE = 100 * 1024 * 1024
# In-thread recover keeps the resumable session alive through brief
# brownouts (losing it forfeits every already-confirmed chunk: the outer
# retry restarts the upload from byte 0). Sleeps are short and capped —
# each blocks a gcs-io executor thread, and with every worker sleeping
# nothing can record progress on the collective deadline — so the total
# in-thread stall is bounded at ~8s before the failure propagates to the
# async retry strategy, whose asyncio.sleep backoff holds no thread.
_MAX_RECOVER_ATTEMPTS = 6
_RECOVER_SLEEP_CAP_SECONDS = 2.0


def _import_gcs_deps():
    try:
        import google.auth  # noqa: F401
        from google.auth.transport.requests import AuthorizedSession  # noqa: F401
        from google.resumable_media import common  # noqa: F401
        from google.resumable_media.requests import (  # noqa: F401
            ChunkedDownload,
            ResumableUpload,
        )
    except ImportError as e:
        raise RuntimeError(
            "GCS support requires google-auth and google-resumable-media "
            "(pip install google-auth google-resumable-media[requests])"
        ) from e
    return google.auth, AuthorizedSession, common, ChunkedDownload, ResumableUpload


def _is_transient(exc: BaseException, common: Any) -> bool:
    """Transient-error taxonomy (reference gcs.py:88-107): HTTP 408/429/5xx,
    connection resets, and invalid-response wrappers are retriable."""
    import requests

    if isinstance(exc, common.InvalidResponse):
        return exc.response.status_code in (408, 429) or (
            500 <= exc.response.status_code < 600
        )
    if isinstance(exc, (requests.ConnectionError, requests.Timeout)):
        return True
    if isinstance(exc, common.DataCorruption):
        return True
    return False


class GCSStoragePlugin(StoragePlugin):
    def __init__(self, root: str) -> None:
        (
            self._google_auth,
            authorized_session_cls,
            self._common,
            self._chunked_download_cls,
            self._resumable_upload_cls,
        ) = _import_gcs_deps()

        bucket, _, prefix = root.partition("/")
        if not bucket:
            raise ValueError(
                f"Invalid GCS root {root!r}; expected 'bucket[/prefix]'"
            )
        self.bucket = bucket
        self.prefix = prefix.strip("/")
        # STORAGE_EMULATOR_HOST (the fake-gcs-server convention) redirects
        # every request to a local emulator with no auth — the CI path for
        # exercising resumable-upload recover and the transient-retry
        # taxonomy against a real HTTP server instead of mocks.
        emulator = os.environ.get("STORAGE_EMULATOR_HOST")
        if emulator:
            if "://" not in emulator:
                emulator = f"http://{emulator}"
            self._base_url = emulator.rstrip("/")
            import requests

            self._session = requests.Session()
        else:
            self._base_url = "https://storage.googleapis.com"
            credentials, _ = self._google_auth.default(
                scopes=["https://www.googleapis.com/auth/devstorage.read_write"]
            )
            self._session = authorized_session_cls(credentials)
        self._executor = ThreadPoolExecutor(
            max_workers=knobs.get_per_rank_io_concurrency(),
            thread_name_prefix="gcs-io",
        )
        self._retry = CollectiveProgressRetryStrategy(scope="gcs")

    # ------------------------------------------------------------------

    def _blob_name(self, path: str) -> str:
        from ..storage_plugin import normalize_object_key

        return normalize_object_key(self.prefix, path)

    def _upload_sync(self, path: str, data: bytes) -> None:
        # Dual annotation (recorder + jax timeline): this runs on a
        # gcs-io executor thread, where the thread-local jax side nests
        # correctly.
        with trace_annotation(
            metric_names.SPAN_STORAGE_WRITE,
            plugin="gcs",
            blob=path,
            bytes=len(data),
        ):
            self._upload_sync_impl(path, data)

    def _upload_sync_impl(self, path: str, data: bytes) -> None:
        blob = self._blob_name(path)
        url = (
            f"{self._base_url}/upload/storage/v1/b/"
            f"{self.bucket}/o?uploadType=resumable"
        )
        # The library's own hidden retry layer (blocking exponential sleeps
        # up to minutes, inside a gcs-io executor thread the collective-
        # progress deadline cannot observe) is disabled: THIS loop's bounded
        # recover plus the async retry strategy are the retry architecture.
        upload = self._resumable_upload_cls(url, _UPLOAD_CHUNK_SIZE)
        # (Constructor takes no retry kwarg in shipped versions; the
        # strategy is an attribute on the transfer object.)
        upload._retry_strategy = self._common.RetryStrategy(max_retries=0)
        stream = io.BytesIO(data)
        upload.initiate(
            self._session,
            stream,
            {"name": blob},
            "application/octet-stream",
            total_bytes=len(data),
        )
        recover_attempts = 0
        while not upload.finished:
            try:
                upload.transmit_next_chunk(self._session)
                recover_attempts = 0
            except Exception as e:
                # Upload-recovery rewind (reference gcs.py:109-122): ask the
                # server how far it got, reposition the stream, continue —
                # bounded and backed off so a sustained brownout propagates
                # out to the collective-progress retry instead of spinning.
                # Covers InvalidResponse AND connection resets/timeouts:
                # with the library's own retry layer disabled, any transient
                # failure that escapes this loop forfeits the resumable
                # session (the outer retry restarts from byte 0).
                if (
                    not _is_transient(e, self._common)
                    or recover_attempts >= _MAX_RECOVER_ATTEMPTS
                ):
                    raise
                time.sleep(
                    min(_RECOVER_SLEEP_CAP_SECONDS, 0.25 * 2**recover_attempts)
                    * (0.5 + random.random())
                )
                upload.recover(self._session)
                recover_attempts += 1
                # Session-recover attempts were previously counted here
                # and dropped; the registry keeps them (they are the
                # leading indicator of a browning-out backend, visible
                # well before the collective deadline trips).
                telemetry.metrics().counter_inc(
                    metric_names.GCS_RECOVER_ATTEMPTS_TOTAL
                )
                # Instant event: places each brownout-recover on the
                # timeline, inside the upload span it interrupted.
                _trace_recorder().instant(
                    metric_names.INSTANT_GCS_RECOVER,
                    blob=blob,
                    attempt=recover_attempts,
                )

    def _download_sync(
        self, path: str, byte_range: Optional[Tuple[int, int]]
    ) -> bytes:
        # Ranged reads were previously invisible to any timeline; the
        # dual annotation covers both whole-blob and ranged downloads.
        args = {"plugin": "gcs", "blob": path}
        if byte_range is not None:
            args["range"] = [int(byte_range[0]), int(byte_range[1])]
        with trace_annotation(metric_names.SPAN_STORAGE_READ, **args):
            return self._download_sync_impl(path, byte_range)

    def _download_sync_impl(
        self, path: str, byte_range: Optional[Tuple[int, int]]
    ) -> bytes:
        blob = urllib.parse.quote(self._blob_name(path), safe="")
        url = (
            f"{self._base_url}/download/storage/v1/b/"
            f"{self.bucket}/o/{blob}?alt=media"
        )
        stream = io.BytesIO()
        if byte_range is not None:
            start, end = byte_range
            download = self._chunked_download_cls(
                url,
                _DOWNLOAD_CHUNK_SIZE,
                stream,
                start=start,
                end=end - 1,  # API takes an inclusive end
            )
        else:
            download = self._chunked_download_cls(
                url, _DOWNLOAD_CHUNK_SIZE, stream
            )
        download._retry_strategy = self._common.RetryStrategy(max_retries=0)
        try:
            while not download.finished:
                download.consume_next_chunk(self._session)
        except self._common.InvalidResponse as e:
            status = getattr(e.response, "status_code", None)
            if status == 404:
                # Normalize to the FS plugin's missing-blob contract so
                # callers (e.g. checksum-table probing) can distinguish
                # absent from unreadable. Definitive: never retried.
                raise FileNotFoundError(path) from e
            if status == 416:
                # Out-of-range ranged read -> the fs/memory plugins' EIO
                # contract (truncation, not partial success); convert
                # --verify and fsck classify on it. Definitive: never
                # retried (OSError is not in the GCS transient taxonomy).
                import errno

                raise OSError(
                    errno.EIO,
                    f"ranged read {byte_range} is outside the blob",
                    path,
                ) from e
            raise
        return stream.getvalue()

    def _delete_sync(self, path: str) -> None:
        blob = urllib.parse.quote(self._blob_name(path), safe="")
        url = (
            f"{self._base_url}/storage/v1/b/"
            f"{self.bucket}/o/{blob}"
        )
        resp = self._session.delete(url)
        if resp.status_code not in (200, 204, 404):
            raise self._common.InvalidResponse(resp, "delete failed")

    # ------------------------------------------------------------------

    async def write(self, write_io: WriteIO) -> None:
        data = bytes(write_io.buf)

        async def op() -> None:
            await run_in_executor(
                self._executor, self._upload_sync, write_io.path, data
            )

        t0 = time.monotonic()
        await self._run_retrying(op)
        observe_io("gcs", "write", len(data), time.monotonic() - t0)

    async def read(self, read_io: ReadIO) -> None:

        async def op() -> bytes:
            return await run_in_executor(
                self._executor,
                self._download_sync,
                read_io.path,
                read_io.byte_range,
            )

        t0 = time.monotonic()
        read_io.buf = memoryview(await self._run_retrying(op))
        observe_io("gcs", "read", read_io.buf.nbytes, time.monotonic() - t0)

    async def delete(self, path: str) -> None:

        async def op() -> None:
            await run_in_executor(self._executor, self._delete_sync, path)

        await self._run_retrying(op)

    async def _run_retrying(self, op):
        """Retry ``op`` on transient GCS errors under the shared
        collective-progress deadline."""

        async def guarded():
            try:
                return await op()
            except Exception as e:
                if _is_transient(e, self._common):
                    raise _TransientGCSError() from e
                raise

        return await self._retry.run(
            guarded, retriable_exceptions=(_TransientGCSError,)
        )

    async def close(self) -> None:
        self._executor.shutdown(wait=False)


class _TransientGCSError(Exception):
    pass
