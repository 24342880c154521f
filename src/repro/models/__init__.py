from .transformer import TransformerConfig, MoEConfig, Rules  # noqa: F401
