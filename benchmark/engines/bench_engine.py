"""The benchmark's DataSource for the recommendation template: hands the
stock Preparator, ALSAlgorithm and Serving the same TrainingData the
stock DataSource would, from ratings the benchmark made in set-up.

Named by a cell's engine.json through `engineFactory`. The event-store
read is bypassed on purpose (PERF.md, Open questions: it wants a cell of
its own). FEED is filled by the child that drives the jobs; each
`read_training` takes the next data set, so no job trains on the
ratings of the job before it.
"""

from dataclasses import dataclass

from predictionio_tpu.controller import DataSource as BaseDataSource
from predictionio_tpu.controller import Engine, FirstServing, Params
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.recommendation.als_algorithm import ALSAlgorithm
from predictionio_tpu.models.recommendation.data_source import TrainingData
from predictionio_tpu.models.recommendation.preparator import Preparator


class Feed:
    """Data sets in the order the jobs take them; wraps around, which
    two different neighbours make safe: the layout cache holds one."""

    def __init__(self, datasets, n_users, n_items):
        if len(datasets) < 2:
            raise ValueError("a feed needs two data sets or more")
        self.datasets = datasets
        self.user_vocab = BiMap({f"u{k}": k for k in range(n_users)})
        self.item_vocab = BiMap({f"i{k}": k for k in range(n_items)})
        self.taken = 0

    def take(self):
        u, i, r = self.datasets[self.taken % len(self.datasets)]
        self.taken += 1
        return TrainingData(user_idx=u, item_idx=i, rating=r,
                            user_vocab=self.user_vocab,
                            item_vocab=self.item_vocab)


FEED = None     # set by child_train before the first job


@dataclass(frozen=True)
class FeedParams(Params):
    appName: str = "bench"


class FeedDataSource(BaseDataSource):
    params_class = FeedParams

    def __init__(self, params=None):
        self.dsp = params

    def read_training(self, ctx):
        if FEED is None:
            raise RuntimeError("bench_engine.FEED was never filled")
        return FEED.take()


def engine():
    return Engine(data_source_class=FeedDataSource,
                  preparator_class=Preparator,
                  algorithm_class_map={"als": ALSAlgorithm},
                  serving_class=FirstServing)
