"""Plain references: one module per model family, and one per federated
algorithm (``<algorithm>.py``, by the name the traffic's job gives) with
the loop that drives them.  Nothing here imports the program under test."""
