"""Recommendation subsystem: SAR + ranking evaluation/tuning.

Reference module replaced: src/recommendation/ — `SAR`/`SARModel`
(SAR.scala:36-205, SARModel.scala:21-167), `RecommendationIndexer`
(RecommendationIndexer.scala:16-130), `RankingAdapter`
(RankingAdapter.scala:66-151), `RankingEvaluator`/`AdvancedRankingMetrics`
(RankingEvaluator.scala:14-151), `RankingTrainValidationSplit`
(RankingTrainValidationSplit.scala:22-337).
"""

from .indexer import RecommendationIndexer, RecommendationIndexerModel
from .sar import SAR, SARModel
from .ranking import (
    RankingAdapter,
    RankingEvaluator,
    RankingTrainValidationSplit,
    ranking_metrics,
)
from .resident import SARTopKScorer

__all__ = [
    "RecommendationIndexer",
    "RecommendationIndexerModel",
    "SAR",
    "SARModel",
    "SARTopKScorer",
    "RankingAdapter",
    "RankingEvaluator",
    "RankingTrainValidationSplit",
    "ranking_metrics",
    "serve_recommender",
]


def __getattr__(name):
    # the server imports the serving package (0.09 s): who serves pays it
    if name == "serve_recommender":
        from .serving import serve_recommender

        return serve_recommender
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
