from .pipeline import SyntheticLMStream, FederatedBatcher
from .partition import dirichlet_vocab_partition, lognormal_sizes
