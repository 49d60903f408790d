"""The plain reference: numpy and python integers over the generated columns
and the acknowledged writes. Imports nothing of the program under test."""
