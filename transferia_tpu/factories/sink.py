"""Sink factory: assembles the full middleware pipeline.

Reference parity: pkg/sink_factory/sink_factory.go — sync middleware order
(:97-134, innermost first): TargetFallbacks, SourceFallbacks, Statistician,
Filter(system tables), NonRowSeparator, Transformation; async wrap
(:179-197): ErrorTracker(MemThrottler(Bufferer|Synchronizer(sync stack))).

Push flow (outermost -> innermost):

  async_push -> ErrorTracker -> [MemThrottler] -> Bufferer/Synchronizer
    -> [Retrier @snapshot] -> Measurer -> Transformation -> NonRowSeparator
    -> Filter -> Statistician -> SourceFallbacks -> TargetFallbacks -> sink

The Bufferer sits at the async/sync boundary so the transformer chain (and
its jitted kernels) sees large merged batches, not source-sized fragments.
"""

from __future__ import annotations

from typing import Optional

from transferia_tpu.abstract.interfaces import AsyncSink, Sinker
from transferia_tpu.abstract.schema import TableID
from transferia_tpu.middlewares.asynchronizer import (
    Bufferer,
    BuffererConfig,
    ErrorTracker,
    MemThrottler,
    Synchronizer,
)
from transferia_tpu.middlewares.sync import (
    Filter,
    Measurer,
    NonRowSeparator,
    Retrier,
    Statistician,
    Transformation as TransformationMW,
    TypeFallbacks,
)
from transferia_tpu.models.endpoint import capability
from transferia_tpu.providers.registry import get_provider
from transferia_tpu.stats.registry import Metrics, SinkerStats
from transferia_tpu.transform.chain import build_chain
from transferia_tpu.typesystem.fallbacks import fallbacks_for

SYSTEM_TABLE_PREFIX = "__"  # system tables excluded from delivery by default


def _system_table_filter(tid: TableID) -> bool:
    return tid.name.startswith(SYSTEM_TABLE_PREFIX) and \
        tid.name not in ("__test",)


def make_sinker(transfer, metrics: Optional[Metrics] = None,
                snapshot_stage: bool = False,
                stats: Optional[SinkerStats] = None,
                post_transform_wrap=None,
                placement_book=None) -> Sinker:
    """Build the synchronous middleware stack over the provider's raw sink."""
    metrics = metrics or Metrics()
    provider = get_provider(transfer.dst_provider(), transfer, metrics)
    raw: Optional[Sinker] = None
    if snapshot_stage:
        raw = provider.snapshot_sinker()
    if raw is None:
        raw = provider.sinker()
    if raw is None:
        raise ValueError(
            f"provider {transfer.dst_provider()!r} has no sink capability"
        )
    version = transfer.type_system_version
    s: Sinker = raw
    tgt_fb = fallbacks_for(transfer.dst_provider(), "target", version)
    if tgt_fb:
        s = TypeFallbacks(s, tgt_fb)
    src_fb = fallbacks_for(transfer.src_provider(), "source", version)
    if src_fb:
        s = TypeFallbacks(s, src_fb)
    from transferia_tpu.metering.agent import (
        InputMetering,
        OutputMetering,
        metering_agent,
    )

    agent = metering_agent(transfer.id)
    s = OutputMetering(s, agent)
    s = Statistician(s, stats or SinkerStats(metrics),
                     transfer_id=transfer.id)
    s = Filter(s, _system_table_filter)
    s = NonRowSeparator(s)
    if post_transform_wrap is not None:
        # injection point for observers of post-transform data (the
        # snapshot loader's inline fingerprint tap)
        s = post_transform_wrap(s)
    chain = build_chain(transfer.transformation,
                        placement_book=placement_book)
    if chain is not None:
        s = TransformationMW(s, chain)
    s = InputMetering(s, agent)
    s = Measurer(s)
    if snapshot_stage:
        s = Retrier(s)
    return s


def make_async_sink(transfer, metrics: Optional[Metrics] = None,
                    snapshot_stage: bool = False,
                    stats: Optional[SinkerStats] = None,
                    post_transform_wrap=None,
                    placement_book=None) -> AsyncSink:
    """MakeAsyncSink (sink_factory.go:31): full async pipeline.

    Providers may supply a native AsyncSink (constructBaseAsyncSink:173);
    otherwise the sync stack is wrapped with Bufferer (when the destination
    opts in via `bufferer_config`) or Synchronizer.  A native async sink
    has no sync stack to host post_transform_wrap; inline validation is
    skipped there (the provider owns its own pipeline).
    """
    metrics = metrics or Metrics()
    provider = get_provider(transfer.dst_provider(), transfer, metrics)
    native = provider.async_sink()
    if native is not None:
        return ErrorTracker(native)
    sync_stack = make_sinker(transfer, metrics, snapshot_stage, stats,
                             post_transform_wrap=post_transform_wrap,
                             placement_book=placement_book)
    buf_cfg = capability(transfer.dst, "bufferer_config", None)
    if buf_cfg is not None and not isinstance(buf_cfg, BuffererConfig):
        buf_cfg = BuffererConfig(**buf_cfg) if isinstance(buf_cfg, dict) \
            else BuffererConfig()
    a: AsyncSink
    if buf_cfg is not None:
        a = Bufferer(sync_stack, buf_cfg)
    else:
        a = Synchronizer(sync_stack)
    limit = capability(transfer.dst, "memory_limit_bytes", None)
    if limit:
        a = MemThrottler(a, limit)
    return ErrorTracker(a)
