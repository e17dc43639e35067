"""The layers the ragged model families share (reference:
``inference/v2/modules/``): :mod:`.attention`, :mod:`.moe`, :mod:`.conv`.
A ``model_implementations/ragged_<family>.py`` imports from here and from
no sibling; nothing here imports a family."""
