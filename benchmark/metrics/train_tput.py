"""Tokens of first-run optimizer steps completed in the window, per held
chip-second. A token is one sequence position (ViT: 197 an image). Steps
that a promotion re-trained because no fork was served do not count."""


def read(w):
    return w.tokens / w.held_s
