"""Plain references and the seeded weights they share with the program."""
