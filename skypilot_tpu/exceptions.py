"""Typed exceptions for the framework.

Capability parity with the reference's error classes
(/root/reference/sky/exceptions.py:1-298), redesigned around TPU slices:
provisioning failures carry a failover history over (tpu_type, zone,
capacity_type) triples rather than VM launchables.
"""
from __future__ import annotations

import enum
from typing import Any, Dict, List, Optional


class SkyTpuError(Exception):
    """Base class for all framework errors."""


class ResourcesUnavailableError(SkyTpuError):
    """No feasible (accelerator, zone, capacity) combination could be provisioned.

    Carries the failover history so callers (managed-jobs recovery, the
    retry_until_up loop) can inspect what was attempted and why it failed.
    """

    def __init__(self,
                 message: str,
                 no_failover: bool = False,
                 failover_history: Optional[List[Exception]] = None) -> None:
        super().__init__(message)
        self.no_failover = no_failover
        self.failover_history: List[Exception] = failover_history or []

    def with_failover_history(
            self, failover_history: List[Exception]
    ) -> 'ResourcesUnavailableError':
        self.failover_history = failover_history
        return self


class ResourcesMismatchError(SkyTpuError):
    """Requested resources do not match the existing cluster's resources."""


class ProvisionPrechecksError(SkyTpuError):
    """Pre-provision validation (quota, credentials, topology) failed."""

    def __init__(self, reasons: List[Exception]) -> None:
        super().__init__(f'Provision prechecks failed: {reasons}')
        self.reasons = reasons


class ProvisionError(SkyTpuError):
    """A cloud API call during provisioning failed."""

    def __init__(self, message: str, *, retryable: bool = True) -> None:
        super().__init__(message)
        self.retryable = retryable


class ClusterNotUpError(SkyTpuError):
    """Operation requires an UP cluster."""

    def __init__(self, message: str, cluster_status: Any = None,
                 handle: Any = None) -> None:
        super().__init__(message)
        self.cluster_status = cluster_status
        self.handle = handle


class ClusterDoesNotExist(SkyTpuError):
    """Named cluster is not in the local state store."""


class ClusterOwnerIdentityMismatchError(SkyTpuError):
    """Cluster was created under a different cloud identity."""


class NotSupportedError(SkyTpuError):
    """Feature is not supported by the selected infra/capacity type."""


class RuntimeVersionSkewError(SkyTpuError):
    """Client and cluster runtime differ by a MAJOR version: the job
    codegen/wire contract may have changed, so exec is refused until
    the cluster runtime is resynced (relaunch or stop/start).  Minor/
    patch skew only warns — the contract is stable within a major."""


class TransientRunnerError(SkyTpuError):
    """A command-runner exec failed in a way that is worth retrying
    (ssh transport blip, connection reset, injected chaos fault) —
    distinct from the command itself exiting non-zero."""

    def __init__(self, message: str, attempts: int = 1) -> None:
        super().__init__(message)
        self.attempts = attempts


class CommandError(SkyTpuError):
    """A remote or local command exited non-zero."""

    def __init__(self, returncode: int, command: str, error_msg: str,
                 detailed_reason: Optional[str] = None) -> None:
        self.returncode = returncode
        self.command = command
        self.error_msg = error_msg
        self.detailed_reason = detailed_reason
        if len(command) > 100:
            command = command[:100] + '...'
        super().__init__(
            f'Command {command} failed with return code {returncode}.'
            f'\n{error_msg}')


class JobError(SkyTpuError):
    pass


class InvalidTaskError(SkyTpuError):
    """Task spec failed validation."""


class InvalidSkyTpuConfigError(SkyTpuError):
    """~/.skytpu/config.yaml failed schema validation."""


class StorageError(SkyTpuError):
    pass


class StorageSpecError(StorageError, ValueError):
    pass


class StorageBucketCreateError(StorageError):
    pass


class StorageBucketGetError(StorageError):
    pass


class StorageUploadError(StorageError):
    pass


class StorageBucketDeleteError(StorageError):
    pass


class StorageNameError(StorageError, ValueError):
    pass


class StorageSourceError(StorageError, ValueError):
    pass


class FetchClusterInfoError(SkyTpuError):
    """Failed to query live instance info from the cloud."""

    class Reason(enum.Enum):
        HEAD = 'HEAD'
        WORKER = 'WORKER'

    def __init__(self, reason: 'FetchClusterInfoError.Reason') -> None:
        super().__init__(f'Failed to fetch cluster info: {reason.value}')
        self.reason = reason


class NetworkError(SkyTpuError):
    pass


class NoCloudAccessError(SkyTpuError):
    """No infra has valid credentials."""


class ManagedJobReachedMaxRetriesError(SkyTpuError):
    pass


class ManagedJobStatusError(SkyTpuError):
    pass


class ServeUserTerminatedError(SkyTpuError):
    pass


class PortDoesNotExistError(SkyTpuError):
    pass


class UserRequestRejectedByPolicy(SkyTpuError):
    """An admin policy rejected this request."""


class NoClusterLaunchedError(SkyTpuError):
    """Sentinel: failover loop never got as far as launching anything."""


class InvalidClusterNameError(SkyTpuError):
    pass


class CloudUserIdentityError(SkyTpuError):
    pass


class ClusterStatusFetchingError(SkyTpuError):
    pass


class JobExitCode(enum.IntEnum):
    """Process exit codes used by CLI/SDK job-status waiters."""
    SUCCEEDED = 0
    FAILED = 100
    NOT_FINISHED = 101
    NOT_FOUND = 102
    CANCELLED = 103

    @classmethod
    def from_job_status(cls, status: Optional[Any]) -> 'JobExitCode':
        if status is None:
            return cls.NOT_FOUND
        # Local import to avoid a cycle with skylet.job_lib.
        from skypilot_tpu.skylet import job_lib  # pylint: disable=import-outside-toplevel
        if status in (job_lib.JobStatus.SUCCEEDED,):
            return cls.SUCCEEDED
        if status in (job_lib.JobStatus.CANCELLED,):
            return cls.CANCELLED
        if status.is_terminal():
            return cls.FAILED
        return cls.NOT_FINISHED


def serialize_exception(e: Exception) -> Dict[str, Any]:
    """Best-effort JSON-safe description of an exception (for logs/telemetry)."""
    return {
        'type': type(e).__name__,
        'message': str(e),
    }
