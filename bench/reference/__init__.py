"""Plain references, one module per model family, and the plain FedBiOAcc
loop that drives them.  Nothing here imports the program under test."""
