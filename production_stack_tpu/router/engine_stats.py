"""Engine-side statistics scraper: polls each engine's /metrics.

Rebuild of reference ``src/vllm_router/stats/engine_stats.py`` (218 LoC):
parses the ``vllm:*`` Prometheus exposition every engine serves —
``num_requests_running`` / ``num_requests_waiting`` / cache usage / prefix
cache hit counters (reference ``EngineStats.from_vllm_scrape:42-85``) — on a
daemon thread (reference ``_scrape_worker:171-182``).

TPU note (SURVEY §5): our engines report **TPU HBM KV usage** as
``vllm:gpu_cache_usage_perc`` for dashboard compatibility and additionally as
``tpu:hbm_kv_usage_perc``; the scraper accepts either name.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional

import requests
from prometheus_client.parser import text_string_to_metric_families

from production_stack_tpu.utils.log import init_logger
from production_stack_tpu.utils.misc import SingletonMeta

logger = init_logger(__name__)


@dataclass
class EngineStats:
    num_running_requests: int = 0
    num_queuing_requests: int = 0
    gpu_prefix_cache_hits: int = 0
    gpu_prefix_cache_queries: int = 0
    gpu_cache_usage_perc: float = 0.0  # on TPU: HBM KV pool usage fraction
    gpu_prefix_cache_hit_rate: float = 0.0
    hbm_headroom_bytes: float = -1.0  # free HBM beyond pool+weights; -1 unknown

    @staticmethod
    def from_vllm_scrape(metrics_text: str) -> "EngineStats":
        """Parse a vLLM-compatible /metrics exposition (reference :42-85)."""
        stats = EngineStats()
        hits = queries = 0.0
        for family in text_string_to_metric_families(metrics_text):
            for sample in family.samples:
                name = sample.name
                value = sample.value
                if name == "vllm:num_requests_running":
                    stats.num_running_requests = int(value)
                elif name == "vllm:num_requests_waiting":
                    stats.num_queuing_requests = int(value)
                elif name in (
                    "vllm:gpu_cache_usage_perc",
                    "tpu:hbm_kv_usage_perc",
                ):
                    stats.gpu_cache_usage_perc = float(value)
                elif name == "tpu:hbm_headroom_bytes":
                    # Autoscale signal (kv/fleet.py recommender).
                    stats.hbm_headroom_bytes = float(value)
                elif name in (
                    "vllm:gpu_prefix_cache_hits_total",
                    "tpu:prefix_cache_hits_total",
                ):
                    hits = value
                elif name in (
                    "vllm:gpu_prefix_cache_queries_total",
                    "tpu:prefix_cache_queries_total",
                ):
                    queries = value
        stats.gpu_prefix_cache_hits = int(hits)
        stats.gpu_prefix_cache_queries = int(queries)
        if queries > 0:
            stats.gpu_prefix_cache_hit_rate = hits / queries
        return stats


class EngineStatsScraper(metaclass=SingletonMeta):
    """Daemon thread scraping every engine's /metrics (reference :88-218)."""

    # Consecutive scrape failures before an endpoint's stats are marked
    # stale and withheld from routing decisions. Below the threshold the
    # last-known stats carry forward (one dropped scrape should not make
    # a kvaware/least-loaded router forget a replica); at or above it,
    # stale numbers are worse than none — the routing logic falls back
    # to its no-stats behavior for that replica.
    STALE_AFTER = 3

    def __init__(self, scrape_interval: float = 10.0):
        if hasattr(self, "_initialized"):
            return
        self._initialized = True
        self.scrape_interval = scrape_interval
        self._stats: Dict[str, EngineStats] = {}
        self._lock = threading.Lock()
        self._running = True
        self._fail_counts: Dict[str, int] = {}
        self._stale: set = set()
        self._thread = threading.Thread(
            target=self._scrape_worker, daemon=True, name="engine-stats-scraper"
        )
        self._thread.start()

    def _scrape_worker(self) -> None:
        from production_stack_tpu.router.service_discovery import (
            get_service_discovery,
        )

        while self._running:
            try:
                endpoints = get_service_discovery().get_endpoint_info()
            except RuntimeError:
                endpoints = []
            fresh: Dict[str, EngineStats] = {}
            stale: set = set()
            with self._lock:
                previous = dict(self._stats)
            for ep in endpoints:
                stats = self._scrape_one(ep.url)
                if stats is not None:
                    fresh[ep.url] = stats
                    self._fail_counts[ep.url] = 0
                    continue
                failures = self._fail_counts.get(ep.url, 0) + 1
                self._fail_counts[ep.url] = failures
                if failures < self.STALE_AFTER and ep.url in previous:
                    # Grace window: carry the last-known stats forward.
                    fresh[ep.url] = previous[ep.url]
                else:
                    stale.add(ep.url)
                    self._count_stale(ep.url)
            # Forget counters for endpoints discovery no longer reports.
            live = {ep.url for ep in endpoints}
            for url in [u for u in self._fail_counts if u not in live]:
                del self._fail_counts[url]
            with self._lock:
                self._stats = fresh
                self._stale = stale
            for _ in range(int(self.scrape_interval * 10)):
                if not self._running:
                    return
                time.sleep(0.1)

    @staticmethod
    def _count_stale(url: str) -> None:
        from production_stack_tpu.router import metrics as router_metrics

        router_metrics.engine_stats_stale.labels(server=url).inc()

    def _scrape_one(self, url: str) -> Optional[EngineStats]:
        try:
            resp = requests.get(f"{url}/metrics", timeout=self.scrape_interval)
            resp.raise_for_status()
            return EngineStats.from_vllm_scrape(resp.text)
        except Exception as e:  # noqa: BLE001
            logger.debug("Scrape failed for %s: %s", url, e)
            return None

    def get_engine_stats(self) -> Dict[str, EngineStats]:
        """Routable stats only: endpoints whose scrapes have failed
        STALE_AFTER consecutive cycles are excluded (their numbers are
        stale — routing on them would pile load onto a replica whose
        true state is unknown)."""
        with self._lock:
            return dict(self._stats)

    def get_stale_endpoints(self) -> "set[str]":
        with self._lock:
            return set(self._stale)

    def get_health(self) -> bool:
        return self._thread.is_alive()

    def close(self) -> None:
        self._running = False


def initialize_engine_stats_scraper(scrape_interval: float = 10.0) -> EngineStatsScraper:
    """A NEW scraper for a new router app. The class is a process-wide
    singleton and an app closes its scraper on cleanup (the thread
    exits), so the second app built in one process would otherwise be
    handed the first one's dead scraper: /health 503, no engine stats."""
    previous = SingletonMeta._instances.get(EngineStatsScraper)
    if previous is not None:
        previous.close()
        SingletonMeta._reset_instance(EngineStatsScraper)
    return EngineStatsScraper(scrape_interval)


def get_engine_stats_scraper() -> EngineStatsScraper:
    return EngineStatsScraper()
