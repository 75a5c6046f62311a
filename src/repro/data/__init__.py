from repro.data.tokenizer import HashTokenizer
from repro.data.corpus import SyntheticRetrievalCorpus, DATASET_SPECS
from repro.data.pipeline import DataPipeline

__all__ = ["HashTokenizer", "SyntheticRetrievalCorpus", "DATASET_SPECS",
           "DataPipeline"]
